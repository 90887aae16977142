"""Model operations of one decode step: per active row, every layer's
projections and MLP (or routed experts) for one token, attention over
its ``keys`` valid positions (``decode_attn``), and the output head."""

from roofline import decode_attn, layer_kinds, prefill_step


def flops(dims: dict, keys) -> float:
    rows = len(keys)
    return sum(n * (2.0 * rows * prefill_step.layer_params(dims, moe)
                    + decode_attn.flops(dims, keys, window))
               for window, moe, n in layer_kinds(dims)) \
        + 2.0 * rows * dims["d"] * dims["vocab"]
