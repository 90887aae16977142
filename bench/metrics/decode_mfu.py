"""Model operations of the window's decode steps (``roofline/
decode_step.py``: active rows and valid positions only) over the decode
programs' device time times the chip's bf16 peak, in percent."""

from lib.measure import DECODE_PROGRAMS
from roofline import decode_step


def read(run):
    if run.window is None:
        return None
    t = run.module_s(DECODE_PROGRAMS)
    steps = run.decode_steps()
    if not t or not steps:
        return None
    ops = sum(decode_step.flops(run.dims, keys) for keys in steps)
    return 100.0 * ops / (t * run.peak["bf16_flops_per_s"])
