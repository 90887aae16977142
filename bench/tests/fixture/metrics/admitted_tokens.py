"""Prompt tokens admitted in the window, prefilled or mapped from shared
pages (a fixture metric: a reader added as one new file)."""


def read(run):
    n = run.counters.get("prefill_tokens", 0) + \
        run.counters.get("shared_tokens", 0)
    return float(n) if n else None
