"""Model operations of the window's admissions (``roofline/
prefill_step.py``: real prompt tokens only) over the admission programs'
device time times the chip's bf16 peak, in percent."""

from lib.measure import PREFILL_PROGRAMS
from roofline import prefill_step


def read(run):
    if run.window is None:
        return None
    t = run.module_s(PREFILL_PROGRAMS)
    work = run.prefills()
    if not t or not work:
        return None
    ops = sum(prefill_step.flops(run.dims, L, s) for L, s in work)
    return 100.0 * ops / (t * run.peak["bf16_flops_per_s"])
