"""mellum2-12b [moe] — Mellum2-12B-A2.5B: every MLP a dropless top-8 of 64
experts, three sliding-window layers (window 1024, plain RoPE) then one
full-attention layer (YaRN RoPE), repeated.

28L d_model=2304 32H (kv=4) head_dim=128 experts 64×896 top-8 vocab=98304,
untied head [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].
"""

from repro.models.layers import Rope
from repro.models.model import ModelConfig

THETA = 5e5
YARN = Rope(theta=THETA, factor=16.0, original_len=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)
PATTERN = (("swa", "moe"), ("swa", "moe"), ("swa", "moe"), ("full", "moe"))


def config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b", family="moe",
        num_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
        head_dim=128, d_ff=896, vocab=98304,
        pattern=PATTERN,
        act="silu", glu=True, rope_theta=THETA,
        rope_kinds=(("swa", Rope(theta=THETA)), ("full", YARN)),
        rms_eps=1e-6, window=1024,
        n_experts=64, top_k=8, norm_topk_prob=True,
        sub_quadratic=False,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-smoke", family="moe",
        num_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, vocab=256,
        pattern=PATTERN,
        act="silu", glu=True, rope_theta=THETA,
        rope_kinds=(("swa", Rope(theta=THETA)),
                    ("full", Rope(theta=THETA, factor=4.0, original_len=32,
                                  beta_fast=32.0, beta_slow=1.0,
                                  attention_factor=1.1386294361119891))),
        rms_eps=1e-6, window=16,
        n_experts=8, top_k=2, norm_topk_prob=True,
        sub_quadratic=False, dtype="float32",
    )
