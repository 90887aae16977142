"""Print, for each host span name in the window of a trace written by
``run.py --trace 1``, how many spans there were, their summed length and
the device-idle time they overlap (``enginespans.idle_within``), in
seconds; then the window's idle gaps labelled by the innermost span,
the engine's ``serve.*`` phases included, at each gap's midpoint
(``devtrace.idle_gaps``).

    python3 bench/tools/phase_idle.py <trace dir>

The engine's ``serve.*`` phases nest (``serve.step`` holds the others),
so the rows of the first table overlap and do not sum to the window's
idle time; the gaps of the second do.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from lib import devtrace, enginespans  # noqa: E402


def phases(w: dict) -> dict:
    """``{name: {"n", "span_s", "idle_s"}}`` of a reduced window."""
    busy = w["modules"] or w["ops"]
    out = {}
    for name in sorted({e["name"] for e in w["host"]}):
        spans = [e for e in w["host"] if e["name"] == name]
        out[name] = {"n": len(spans),
                     "span_s": sum(e["dur"] for e in spans) / 1e9,
                     "idle_s": enginespans.idle_within(busy, spans) / 1e9}
    return out


def main(trace_dir: str) -> None:
    w = devtrace.window(enginespans.load(trace_dir),
                        enginespans.WINDOW_SPAN)
    s = devtrace.summary(w)
    print(f"window {s['window_s']} s, busy {s['busy_s']} s, "
          f"idle {s['window_s'] - s['busy_s']} s")
    print(f"{'span':24s} {'n':>7s} {'span_s':>12s} {'idle_s':>12s} "
          f"{'idle_ms_each':>12s}")
    for name, r in phases(w).items():
        print(f"{name:24s} {r['n']:7d} {r['span_s']:12.6f} "
              f"{r['idle_s']:12.6f} {1e3 * r['idle_s'] / r['n']:12.6f}")
    print("idle gaps by midpoint:")
    for name, sec in s["breakdown"]["idle_gaps"]:
        print(f"{name:24s} {sec:12.6f}")


if __name__ == "__main__":
    main(sys.argv[1])
