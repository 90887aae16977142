"""Share of the prompt tokens admitted in the window that were mapped
from resident pages instead of prefilled: the engine's counters
``shared_tokens / (shared_tokens + prefill_tokens)``, in percent."""


def read(run):
    shared = run.counters.get("shared_tokens", 0)
    total = shared + run.counters.get("prefill_tokens", 0)
    return 100.0 * shared / total if total else None
