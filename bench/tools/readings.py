"""Readings for a cell's correctness limit: for each seed, one run of the
cell as ``run.py`` makes it, the gap it reads, and the control's gap on
the same prompts and served tokens (the reference computed with float8
weights, ``reference/dense.py``).  All seeds in one process, so the
programs compile once.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control 1]

Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from lib import check, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out, seqs = run.run_cell(cell, seed, args.seconds, False,
                                 log=lambda *a: None)
        line = {"seed": seed, "correct": out["correct"],
                "gap_max": out["checks"]["gap_max"]["value"],
                "requests": len(seqs),
                "tokens": sum(len(t) - s for t, s in seqs),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if args.control:
            line.update(check.readings(cell.reference(), cell.config, seed,
                                       seqs))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
