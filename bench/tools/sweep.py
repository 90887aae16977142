"""Find a cell's knee: the highest arrival rate at which the backlog does
not grow over the window.  One process builds and warms the cell once,
then offers each rate for ``--seconds`` and drains before the next.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 4,8,16

Per rate it prints requests due, those with a first token by the close,
those still waiting at half time and at the close, the time to first
token of the first and the second half of the window's requests, and
tokens per second.  A backlog that grows shows as a second half slower
than the first and more waiting at the close than at half time.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from lib import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    run.device_info(cell.chips)
    run.use_cache()

    from lib import traffic
    from lib.driver import Driver
    from lib.measure import p
    from repro.serve.engine import Request

    def annotate(name):
        return contextlib.nullcontext()
    rates = [float(r) for r in args.rates.split(",")]
    cfg, dims, params, eng = run.build(cell, args.seed, annotate)
    opts = cell.config["engine"]

    def plan_at(rate):
        return traffic.make_plan(cell.traffic, {"rate_per_s": rate},
                                 seed=args.seed, seconds=args.seconds,
                                 vocab=cfg.vocab, budget=opts["budget"],
                                 bench_dir=cell.bench_dir)
    run.warm(eng, plan_at(max(rates)), Request, opts, args.seed, annotate)
    rid0 = 0
    for rate in rates:
        drv = Driver(eng, plan_at(rate), request_cls=Request,
                     annotate=annotate, rid_base=rid0)
        half = {}

        def step_hook(d=drv, h=half):
            if "n" not in h and d.now() >= args.seconds / 2:
                h["n"] = sum(1 for r in d.records
                             if r.due < d.now() and r.first is None)
        orig = drv.step

        def step(next_turns=True, orig=orig):
            orig(next_turns)
            step_hook()
        drv.step = step
        close = drv.run_window(args.seconds, open_loop=True)
        due = [r for r in drv.records if r.due < close]
        waiting = sum(1 for r in due if r.first is None)
        tokens = sum(1 for r in drv.records for t in r.times if t <= close)
        end = drv.drain(close)
        while not eng.done:
            eng.step()
        mid = args.seconds / 2
        tt = [((r.first if r.first is not None else end) - r.due) * 1e3
              for r in due]
        first = [t for t, r in zip(tt, due) if r.due < mid]
        second = [t for t, r in zip(tt, due) if r.due >= mid]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "answered_by_close": len(due) - waiting,
            "waiting_at_half": half.get("n"), "waiting_at_close": waiting,
            "ttft_p50_ms_first_half": p(first, 50),
            "ttft_p95_ms_first_half": p(first, 95),
            "ttft_p50_ms_second_half": p(second, 50),
            "ttft_p95_ms_second_half": p(second, 95),
            "tokens_per_s": tokens / close}), flush=True)
        rid0 += len(drv.records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
