"""Device time of the admission programs (prefill, partial prefill after
shared pages, and the cache relayout) per admission in the window."""

from lib.measure import PREFILL_PROGRAMS


def read(run):
    n = run.counters.get("prefills", 0)
    if run.window is None or not n:
        return None
    t = run.module_s(PREFILL_PROGRAMS)
    return 1e3 * t / n if t else None
