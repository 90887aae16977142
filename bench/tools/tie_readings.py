"""Readings of a mixture-of-experts cell's correctness check: for each
seed, one run of the cell as ``run.py`` makes it (its ``correct`` and the
numbers it compared with the cell file's limits), then the control put
in the program's place in the harness's own comparison: the e4m3
control's numbers from ``check.readings`` on the same prompts and served
tokens, held to the same limits (``control_correct`` is what ``run.py``
would say of a run whose gaps were the control's).

With ``--ties`` it also reads the gaps by router margin: at every
compared position the served token's gap, the control's gap and the
position's smallest router margin over the layers (``position_readings``
of the cell's reference module), and for each margin threshold the
widest and mean gap of both over the positions at or above it and the
share of positions kept.  With ``--out`` those per-position arrays are
written there (``<cell>.<seed>.npz``).  All seeds in one process, so the
programs compile once.

    python3 bench/tools/tie_readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--ties 0,0.005,0.01] [--out <dir>]

Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from lib import check, spec  # noqa: E402


def by_tie(gap, ctl, margin, ties):
    """Widest and mean gap, sound and control, at each threshold."""
    rows = {}
    for t in ties:
        k = margin >= t
        rows[str(t)] = {"kept": float(k.mean()),
                        "gap_max": float(gap[k].max()),
                        "gap_mean": float(gap[k].mean()),
                        "control_gap_max": float(ctl[k].max()),
                        "control_gap_mean": float(ctl[k].mean())}
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ties", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    ref = cell.reference()
    limits = cell.load["limits"]
    out_dir = pathlib.Path(args.out) if args.out else None
    if out_dir:
        out_dir.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        out, seqs = run.run_cell(cell, seed, args.seconds, False,
                                 log=lambda *a: None)
        read = check.readings(ref, cell.config, seed, seqs)
        control = {k: read["control_" + k] for k in limits}
        line = {"seed": seed, "correct": out["correct"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "control": control,
                "control_correct": all(control[k] <= lim
                                       for k, lim in limits.items()),
                "requests": len(seqs),
                "positions": sum(len(t) - s for t, s in seqs),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if args.ties or out_dir:
            gap, ctl, mar = (np.concatenate(a) for a in
                             ref.position_readings(cell.config, seed, seqs,
                                                   control=True))
            if args.ties:
                line["by_tie"] = by_tie(gap, ctl, mar, [
                    float(t) for t in args.ties.split(",")])
            if out_dir:
                np.savez(out_dir / f"{args.workload}.{seed}.npz", gap=gap,
                         control=ctl, margin=mar)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
