"""Model operations of one admission: every layer over the prompt's
unshared tokens (projections, MLP, causal attention from
``flash_attn``), and the output head over the last token only, which is
all the next token needs."""

from roofline import flash_attn


def layer_params(dims: dict) -> int:
    d, q, kv = dims["d"], dims["heads"] * dims["dh"], dims["kv"] * dims["dh"]
    return d * q + 2 * d * kv + q * d + 3 * d * dims["ff"]


def flops(dims: dict, length: int, shared: int = 0) -> float:
    tokens = length - shared
    per_layer = 2.0 * tokens * layer_params(dims) \
        + flash_attn.flops(dims, length, shared)
    return dims["layers"] * per_layer + 2.0 * dims["d"] * dims["vocab"]
