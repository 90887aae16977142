"""Roofline time of the window's decode attention (``roofline/
decode_attn.py``, one call per layer per step over its rows' valid
positions, capped at the layer's window) over the device time of the
paged decode kernel, in percent.  Bound by bandwidth."""

from lib.measure import DECODE_KERNEL
from roofline import decode_attn, layer_kinds


def read(run):
    if run.window is None:
        return None
    t = run.kernel_s(DECODE_KERNEL)
    steps = run.decode_steps()
    if not t or not steps:
        return None
    ideal = sum(n * sum(decode_attn.roofline_s(run.dims, run.peak, keys, w)
                        for keys in steps)
                for w, _, n in layer_kinds(run.dims))
    return 100.0 * ideal / t
