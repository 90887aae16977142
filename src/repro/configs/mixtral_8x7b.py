"""mixtral-8x7b [moe] — 8 experts top-2, sliding-window attention.

32L d_model=4096 32H (kv=8) d_ff=14336 vocab=32000 [arXiv:2401.04088].
long_500k runs: SWA window 4096 → rolling KV buffer, O(window) decode.
"""

from repro.models.model import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x7b", family="moe",
        num_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
        head_dim=128, d_ff=14336, vocab=32000,
        pattern=(("swa", "moe"),),
        act="silu", glu=True, rope_theta=1e6,
        window=4096,
        n_experts=8, top_k=2,
        sub_quadratic=True,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="mixtral-smoke", family="moe",
        num_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab=256,
        pattern=(("swa", "moe"),),
        act="silu", glu=True, window=16,
        n_experts=4, top_k=2,
        sub_quadratic=True, dtype="float32",
    )
