"""Print the structure of a profiler trace written by ``run.py --trace 1``
(planes, lines, event counts and the most frequent names), and save a
short stretch of it, reduced to the benchmark's event records, as a test
fixture.

    python3 bench/tools/trace_summary.py <trace dir> <out.json> [ms]
"""

from __future__ import annotations

import collections
import glob
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from lib import devtrace  # noqa: E402


def main(trace_dir: str, out: str, ms: float = 200.0) -> None:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print(f"{path}: {os.path.getsize(path)} bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter(ev.name for ev in line.events)
            n = sum(names.values())
            print(f"{plane.name!r} / {line.name!r}: {n} events")
            for name, c in names.most_common(12):
                print(f"    {c:8d}  {name[:110]}")
    events = devtrace.load_xplane(trace_dir)
    w = devtrace.window(events, "bench.window")
    mid = (w["t0"] + w["t1"]) // 2
    a, b = mid, mid + int(ms * 1e6)
    keep = devtrace.clip([e for e in events if e["name"] != "bench.window"],
                         a, b)
    keep.append({"plane": "/host:CPU", "line": "bench", "name":
                 "bench.window", "t0": a, "dur": b - a})
    devtrace.save(keep, out)
    print(f"saved {len(keep)} events of {ms} ms to {out}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(float(x) for x in sys.argv[3:]))
