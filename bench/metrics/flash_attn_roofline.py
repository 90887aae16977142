"""Roofline time of the window's prefill attention (``roofline/
flash_attn.py``, every layer of every admission, each at its own
window) over the device time of the flash kernel, in percent.  Bound by
compute at these lengths."""

from lib.measure import FLASH_KERNEL
from roofline import flash_attn, layer_kinds


def read(run):
    if run.window is None:
        return None
    t = run.kernel_s(FLASH_KERNEL)
    work = run.prefills()
    if not t or not work:
        return None
    ideal = sum(n * sum(flash_attn.roofline_s(run.dims, run.peak, L, s, w)
                        for L, s in work)
                for w, _, n in layer_kinds(run.dims))
    return 100.0 * ideal / t
