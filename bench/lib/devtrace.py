"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event records; everything after that is pure arithmetic on
those records, so a small recorded trace (``tests/data``) checks it on
the CPU.

A record is ``{"plane", "line", "name", "t0", "dur"}`` with times in
nanoseconds on the trace's clock.  Device records come from the planes
named ``/device:TPU:<n>``: the ``XLA Modules`` line holds one event per
program run (named after the jitted function), the ``XLA Ops`` line one
per operation (a Pallas kernel under its kernel's name).  Host records
are the ``TraceAnnotation`` spans of the benchmark's own calls.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES = "XLA Modules"
OPS = "XLA Ops"
HOST_PREFIX = "bench."


def load_xplane(trace_dir: str) -> List[dict]:
    """Device module and op events, and the benchmark's host spans, of
    the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if dev and line.name not in (MODULES, OPS):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(HOST_PREFIX):
                    continue
                # an op event is named by its whole HLO instruction:
                # keep the instruction's name, "%fusion.12"
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name.split(" = ", 1)[0],
                            "t0": int(ev.start_ns),
                            "dur": int(ev.duration_ns)})
    return out


def save(events: Sequence[dict], path: str) -> None:
    with open(path, "w") as f:
        json.dump(list(events), f)


def load(path: str) -> List[dict]:
    with open(path) as f:
        return json.load(f)


def host_span(events: Iterable[dict], name: str) -> Tuple[int, int]:
    """``(t0, t1)`` of the one host span called ``name``."""
    spans = [e for e in events if e["name"] == name
             and not DEVICE_PLANE.match(e["plane"])]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} host spans named {name!r}")
    return spans[0]["t0"], spans[0]["t0"] + spans[0]["dur"]


def device(events: Iterable[dict], line: str) -> List[dict]:
    return [e for e in events if e["line"] == line
            and DEVICE_PLANE.match(e["plane"])]


def clip(events: Iterable[dict], t0: int, t1: int) -> List[dict]:
    """Events cut to ``[t0, t1]``; those outside it dropped."""
    out = []
    for e in events:
        a, b = max(e["t0"], t0), min(e["t0"] + e["dur"], t1)
        if b > a:
            out.append({**e, "t0": a, "dur": b - a})
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[dict]) -> float:
    """Union of device op intervals, averaged over the device planes."""
    planes = {e["plane"] for e in events}
    if not planes:
        return 0.0
    tot = 0
    for p in planes:
        tot += sum(b - a for a, b in union(
            (e["t0"], e["t0"] + e["dur"]) for e in events
            if e["plane"] == p))
    return tot / len(planes)


def total_ns(events: Iterable[dict], pattern: str) -> int:
    """Summed duration of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(e["dur"] for e in events if rx.search(e["name"]))


_SUFFIX = re.compile(r"[.(]\d+\)?$")
# ops that contain other ops, whose time the contained ops already show
CONTAINERS = re.compile(r"^%(while|conditional|call)\b")


def top_ops(ops: Sequence[dict], n: int = 10) -> List[list]:
    """The ``n`` operation names with the most device time, in seconds
    (numbered HLO instances such as ``%fusion.12`` pooled by name; loops
    and calls left out, since the ops inside them are listed)."""
    acc: Dict[str, int] = {}
    for e in ops:
        if CONTAINERS.match(e["name"]):
            continue
        key = _SUFFIX.sub("", e["name"])
        acc[key] = acc.get(key, 0) + e["dur"]
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(busy_events: Sequence[dict], host: Sequence[dict],
              t0: int, t1: int, n: int = 10) -> List[list]:
    """Idle device time in ``[t0, t1]``, pooled by what the host was doing
    at each gap's midpoint (the innermost benchmark span there; ``"none"``
    where there is none), the ``n`` largest in seconds."""
    planes = sorted({e["plane"] for e in busy_events}) or [None]
    spans = sorted(host, key=lambda e: e["dur"])     # innermost first
    acc: Dict[str, float] = {}
    for p in planes:
        iv = union((e["t0"], e["t0"] + e["dur"]) for e in busy_events
                   if e["plane"] == p)
        edges = [t0] + [x for ab in iv for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            label = next((s["name"] for s in spans
                          if s["t0"] <= mid <= s["t0"] + s["dur"]), "none")
            acc[label] = acc.get(label, 0.0) + (b - a) / 1e9 / len(planes)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            ][:n]


def window(events: Sequence[dict], span: str) -> Optional[dict]:
    """The trace reduced to one window: device modules and ops clipped to
    the host span ``span``, the benchmark's host spans inside it, and the
    window's bounds."""
    t0, t1 = host_span(events, span)
    host = [e for e in events if not DEVICE_PLANE.match(e["plane"])
            and e["name"] != span]
    return {"t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9,
            "modules": clip(device(events, MODULES), t0, t1),
            "ops": clip(device(events, OPS), t0, t1),
            "host": clip(host, t0, t1)}


def summary(w: dict) -> dict:
    """``busy_s`` and the ``breakdown`` of a reduced window."""
    busy = w["modules"] or w["ops"]
    return {"busy_s": busy_ns(busy) / 1e9, "window_s": w["window_s"],
            "breakdown": {
                "device_ops": top_ops(w["ops"]),
                "idle_gaps": idle_gaps(busy, w["host"], w["t0"], w["t1"])}}
