"""The engine's host phases in a traced run, and the device's idle time
inside them.

The engine names each stretch of its host work with a ``serve.*``
``TraceAnnotation`` (``prof/trace.py`` ``host_span``): ``serve.step``
holds one whole tick, its children the phases of that tick.  They land
in the same ``.xplane.pb`` as the device events, on the same clock.
``devtrace.load_xplane`` keeps only the benchmark's own ``bench.*``
host spans; this module reads the engine's from the same file, in the
same record form, and puts idle device time down to them by overlap
(``idle_within``), where ``devtrace.idle_gaps`` labels a whole gap by
its midpoint.

A program that records no ``serve.*`` span reads nothing here: the
readers return ``None`` and raise nothing.
"""

from __future__ import annotations

import glob
import os
import pathlib
from typing import Iterable, List, Optional, Sequence, Tuple

from lib import devtrace

ENGINE_PREFIX = "serve."
STEP_SPAN = "serve.step"
WINDOW_SPAN = "bench.window"        # the span run.py measures inside
# where run.py writes its traces, one directory per cell
TRACE_ROOT = pathlib.Path(__file__).resolve().parents[1] / "out" / "trace"


def newest_xplane(root: str) -> Optional[str]:
    """The ``.xplane.pb`` written last under ``root/<cell>/``, or
    ``None``."""
    paths = glob.glob(os.path.join(
        str(root), "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def host_events(path: str,
                prefixes: Tuple[str, ...] = (ENGINE_PREFIX, WINDOW_SPAN)
                ) -> List[dict]:
    """The host events of one ``.xplane.pb`` whose names start with one
    of ``prefixes``, as ``devtrace`` records."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "t0": int(ev.start_ns),
                                "dur": int(ev.duration_ns)})
    return out


def load(trace_dir: str) -> List[dict]:
    """``devtrace.load_xplane`` of ``trace_dir`` with the engine's
    ``serve.*`` spans added."""
    events = devtrace.load_xplane(trace_dir)
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return events + host_events(path, (ENGINE_PREFIX,))


def idle_within(busy_events: Sequence[dict],
                spans: Iterable[dict]) -> float:
    """Idle device ns inside the union of ``spans`` (host spans, say all
    the ``serve.step`` spans): the union's length less the device's busy
    time that overlaps it, averaged over the device planes, as
    ``devtrace.busy_ns`` averages."""
    cover = devtrace.union((s["t0"], s["t0"] + s["dur"]) for s in spans)
    total = sum(b - a for a, b in cover)
    planes = {e["plane"] for e in busy_events}
    if not planes:
        return float(total)
    idle = 0
    for p in planes:
        busy = devtrace.union((e["t0"], e["t0"] + e["dur"])
                              for e in busy_events if e["plane"] == p)
        overlap, j = 0, 0
        for a, b in cover:
            while j < len(busy) and busy[j][1] <= a:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < b:
                overlap += min(b, busy[k][1]) - max(a, busy[k][0])
                k += 1
        idle += total - overlap
    return idle / len(planes)


def idle_per_step_ms(window: dict, spans: Sequence[dict]) -> Optional[float]:
    """Device-idle ms inside the ``serve.step`` spans of a reduced
    window (``devtrace.window``), by overlap, per ``serve.step`` that
    starts in it (one cut at the window's start started before it).
    ``None`` where no step starts in the window."""
    t0, t1 = window["t0"], window["t1"]
    steps = devtrace.clip((e for e in spans if e["name"] == STEP_SPAN),
                          t0, t1)
    n = sum(1 for e in steps if e["t0"] > t0)
    if not n:
        return None
    busy = window["modules"] or window["ops"]
    return idle_within(busy, steps) / 1e6 / n


def host_idle_ms(run, trace_root=TRACE_ROOT) -> Optional[float]:
    """``idle_per_step_ms`` of a traced run, the engine's spans read from
    the trace the run wrote last under ``trace_root``.  ``None`` where
    the run was not traced, that trace is not the run's (its window span
    differs), or the program records no ``serve.step`` span."""
    if run.window is None:
        return None
    path = newest_xplane(trace_root)
    if path is None:
        return None
    events = host_events(path)
    windows = [e for e in events if e["name"] == WINDOW_SPAN]
    if [(e["t0"], e["t0"] + e["dur"]) for e in windows] != \
            [(run.window["t0"], run.window["t1"])]:
        return None
    return idle_per_step_ms(run.window, events)
