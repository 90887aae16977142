"""Model operations of one decode step: per active row, every layer's
projections and MLP for one token, attention over its ``keys`` valid
positions (``decode_attn``), and the output head."""

from roofline import decode_attn, prefill_step


def flops(dims: dict, keys) -> float:
    rows = len(keys)
    per_layer = 2.0 * rows * prefill_step.layer_params(dims) \
        + decode_attn.flops(dims, keys)
    return dims["layers"] * per_layer \
        + 2.0 * rows * dims["d"] * dims["vocab"]
