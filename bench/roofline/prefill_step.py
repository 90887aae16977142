"""Model operations of one admission: every layer over the prompt's
unshared tokens (projections, MLP or the experts a token is routed to,
causal attention from ``flash_attn``), and the output head over the last
token only, which is all the next token needs."""

from roofline import flash_attn, layer_kinds


def layer_params(dims: dict, moe: bool = False) -> int:
    """Weights one token passes through in one layer: the attention
    projections and the MLP, or, in a layer of experts, the router, its
    ``top_k`` experts and the shared expert."""
    d, q, kv = dims["d"], dims["heads"] * dims["dh"], dims["kv"] * dims["dh"]
    attn = d * q + 2 * d * kv + q * d
    if not moe:
        return attn + 3 * d * dims["ff"]
    return attn + d * dims["experts"] \
        + 3 * d * (dims["top_k"] * dims["expert_ff"]
                   + dims.get("shared_ff", 0))


def flops(dims: dict, length: int, shared: int = 0) -> float:
    tokens = length - shared
    return sum(n * (2.0 * tokens * layer_params(dims, moe)
                    + flash_attn.flops(dims, length, shared, window))
               for window, moe, n in layer_kinds(dims)) \
        + 2.0 * dims["d"] * dims["vocab"]
