"""llama4-maverick-400b-a17b [moe] — 128 experts top-1 + shared expert,
iRoPE layout (chunked-local attention with RoPE on 3/4 of layers, global
NoPE attention on every 4th), MoE on every other layer.

48L d_model=5120 40H (kv=8) d_ff=8192 vocab=202048
[hf:meta-llama/Llama-4-Maverick; unverified].
long_500k skipped: the global-NoPE layers keep decode O(seq).
"""

from repro.models.model import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="llama4-maverick-400b-a17b", family="moe",
        num_layers=48, d_model=5120, n_heads=40, n_kv_heads=8,
        head_dim=128, d_ff=8192, vocab=202048,
        pattern=(("chunked", "dense"), ("chunked", "moe"),
                 ("chunked", "dense"), ("global_nope", "moe")),
        act="silu", glu=True, rope_theta=5e5,
        chunk=8192,
        n_experts=128, top_k=1, shared_expert=True,
        sub_quadratic=False,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="llama4-smoke", family="moe",
        num_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab=256,
        pattern=(("chunked", "dense"), ("chunked", "moe"),
                 ("chunked", "dense"), ("global_nope", "moe")),
        act="silu", glu=True, chunk=16,
        n_experts=4, top_k=1, shared_expert=True,
        sub_quadratic=False, dtype="float32",
    )
