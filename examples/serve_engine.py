"""Continuous-batching serving driver: requests with different prompt
lengths, arrival ticks and budgets share one standing batched KV cache —
the engine admits each into a free slot (per-request prefill packed into
slot i in place), advances all active slots with one fused decode step
per tick at per-sequence ring positions, streams tokens out, and reuses
retired slots for the next arrival.

Admission (PREFILL_KERNEL + SLOT_INSERT) and decode (DECODE_KERNEL) run
on separate profiled queues, so the profiler shows their interleaving —
the paper's two-queue pattern applied to mixed-depth inference traffic.
For the lockstep-batch reference driver see ``serve_decode.py``.

Run:  PYTHONPATH=src python examples/serve_engine.py --requests 8

``--full`` serves the published-width config (``get_config``; SmolLM-360M
unless ``--arch`` says otherwise) at a real size — 8 slots, a 2048-position
budget, prompts of 128–1024 tokens with 32 new tokens each — which is for
the chip, not the CPU:

    PYTHONPATH=src python examples/serve_engine.py --full --paged \
        --attn-impl pallas

Prefill and decode both run ``--attn-impl``.  Lane errors are not retried
(``max_submission_retries=0``): a compile or device error stops the run
with its own message, and the example exits non-zero if any request
ends ``FAILED`` without a ``--deadline-ticks`` having asked for that.

``--metrics`` prints the end-of-run metrics registry (latency
percentiles in ticks, counters, gauges) plus the per-request span
Gantt; ``--trace out.json`` writes the merged device+request timeline
in Chrome ``trace_event`` format — load it at ``ui.perfetto.dev``.
"""

import argparse
import dataclasses
import sys

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.compile_cache import use_compile_cache
from repro.core.errors import err_string
from repro.models.model import init_params
from repro.prof import (Prof, compile_summary, export_perfetto,
                        queue_chart, render_request_gantt)
from repro.serve.engine import Request, ServeEngine, Status

# --full defaults: SmolLM-360M at a size one TPU v5e serves for real
FULL = dict(arch="smollm-360m", slots=8, budget=2048, page_size=128,
            prompt_lens=(128, 1024), new_tokens=(32, 32))
SMOKE = dict(arch="mixtral-8x7b", slots=4, budget=96, page_size=8,
             prompt_lens=(8, 24), new_tokens=(6, 20))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="published-width config (get_config) at a real "
                         "size; the default is the tiny smoke config")
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: mixtral-8x7b smoke, "
                         "smollm-360m with --full)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode batch width (standing cache slots; "
                         "default 4, 8 with --full)")
    ap.add_argument("--budget", type=int, default=None,
                    help="decode position budget per slot (default 96, "
                         "2048 with --full)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--mean-gap", type=float, default=2.0,
                    help="Poisson mean inter-arrival gap in ticks")
    ap.add_argument("--attn-impl", default="xla", choices=["xla", "pallas"],
                    help="attention for prefill and decode: jnp reference "
                         "or the Pallas flash / fused decode kernels")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV pool instead of dense "
                         "per-slot rings")
    ap.add_argument("--page-size", type=int, default=None,
                    help="positions per KV page (paged mode; default 8, "
                         "128 with --full)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="arena pages per cache kind (default: dense-"
                         "equivalent full provision; smaller values "
                         "oversubscribe and exercise preemption)")
    ap.add_argument("--system-prompt", type=int, default=0, metavar="N",
                    help="prepend one shared N-token system prompt to "
                         "every request (paged mode: full pages of it "
                         "are served from shared physical pages with "
                         "copy-on-write)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    metavar="D",
                    help="give every request a D-tick service deadline: "
                         "requests unfinished D ticks after submission "
                         "fail with DEADLINE_EXCEEDED instead of "
                         "occupying the queue (the batch streams on)")
    ap.add_argument("--buckets", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="draw every jitted step shape from the static "
                         "bucket ladders (packed decode widths, prompt "
                         "length buckets — one compile per rung); "
                         "--no-buckets restores exact shapes, i.e. one "
                         "retrace per distinct prompt length")
    ap.add_argument("--warmup", action="store_true",
                    help="eagerly compile the bucket ladders before "
                         "serving (compile hits land up front, not on "
                         "first use)")
    ap.add_argument("--autotune", action="store_true",
                    help="after the normal run, serve the same requests "
                         "again through an autotuned engine (attn_impl="
                         "'auto': per-shape kernel configs resolved from "
                         "the measured cache or the cost model at "
                         "warmup) and assert the streams are "
                         "byte-identical to the untuned run")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the merged device+request timeline as "
                         "Chrome/Perfetto trace_event JSON")
    ap.add_argument("--metrics", action="store_true",
                    help="print the end-of-run metrics table (latency "
                         "percentiles, counters, gauges) and the "
                         "per-request span Gantt")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    size = FULL if args.full else SMOKE
    for name in ("arch", "slots", "budget", "page_size"):
        if getattr(args, name) is None:
            setattr(args, name, size[name])

    use_compile_cache()
    get = get_config if args.full else get_smoke_config
    cfg = dataclasses.replace(get(args.arch), attn_impl=args.attn_impl)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.poisson(args.mean_gap, size=args.requests))
    system = [int(t) for t in rng.integers(0, cfg.vocab,
                                           args.system_prompt)]
    lo, hi = size["prompt_lens"]
    new_lo, new_hi = size["new_tokens"]
    reqs = [Request(i,
                    system +
                    [int(t) for t in rng.integers(0, cfg.vocab,
                                                  rng.integers(lo, hi + 1))],
                    int(rng.integers(new_lo, new_hi + 1)), arrival=int(a),
                    deadline_ticks=args.deadline_ticks)
            for i, a in enumerate(arrivals)]

    eng = ServeEngine(cfg, params, n_slots=args.slots, budget=args.budget,
                      paged=args.paged, page_size=args.page_size,
                      pool_pages=args.pool_pages, buckets=args.buckets,
                      max_submission_retries=0)
    if args.warmup:
        eng.warmup()
    prof = Prof()
    prof.start()
    streams = eng.run(reqs)
    prof.stop()

    seq_of = {s.rid: s for s in eng.sequences}
    for r in reqs:
        s = streams[r.rid]
        line = (f"req {r.rid:2d}: arrival={r.arrival:3d} "
                f"prompt={len(r.prompt):2d} budget={r.max_new_tokens:2d} "
                f"→ {len(s):2d} tokens: {s[:8]}{'…' if len(s) > 8 else ''}")
        err = seq_of[r.rid].error
        if err is not None:
            line += f"  [FAILED: {err_string(err.code)}]"
        print(line)
    st = eng.stats
    util = st["decoded_tokens"] / max(1, st["decode_steps"] * args.slots)
    if args.metrics:
        # full registry view: tick-based latency percentiles, gauges
        # with their high-water marks, and every counter
        print(f"\n{eng.tick} ticks, slot utilization {util:.2f}")
        print(eng.metrics.render(), end="")
        if args.paged:
            print(f"resident KV {eng.cache_mgr.resident_bytes():,} bytes")
    else:
        print(f"\n{eng.tick} ticks, {st['prefills']} prefills, "
              f"{st['decode_steps']} decode steps, "
              f"{st['decoded_tokens']} decoded tokens "
              f"(slot utilization {util:.2f}), {st['failures']} failed")
        if args.paged:
            print(f"paged pool: {st['preemptions']} preemptions, "
                  f"{st['swap_ins']} swap-ins, resident KV "
                  f"{eng.cache_mgr.resident_bytes():,} bytes")
            print(f"prefix sharing: {st['prefix_hits']} hits, "
                  f"{st['shared_tokens']} shared of "
                  f"{st['shared_tokens'] + st['prefill_tokens']} prompt "
                  f"tokens, {st['cow_copies']} CoW copies")

    compiles = " ".join(f"{k}={v}" for k, v in st["compiles"].items())
    print(f"jit compiles ({'bucketed' if args.buckets else 'exact shapes'})"
          f": {compiles or 'none'}")

    prof.add_queue("Admit", eng.q_admit)
    prof.add_queue("Decode", eng.q_decode)
    prof.add_events("Compile", eng.compile_events)
    prof.calc()
    print(prof.get_summary())
    print(compile_summary(prof), end="")
    print(queue_chart(prof, width=80))
    if args.metrics:
        print(render_request_gantt(eng.trace, width=80))
    if args.trace:
        export_perfetto(args.trace, prof=prof, trace=eng.trace)
        print(f"perfetto trace written to {args.trace}")

    if args.autotune:
        # one numeric path: the autotuned engine resolves every shape to
        # a concrete kernel config at warmup, then must reproduce the
        # untuned run's streams byte-for-byte
        eng2 = ServeEngine(cfg, params, n_slots=args.slots,
                           budget=args.budget, paged=args.paged,
                           page_size=args.page_size,
                           pool_pages=args.pool_pages,
                           buckets=args.buckets, autotune=True,
                           max_submission_retries=0)
        eng2.warmup()
        print(f"\nautotune: {len(eng2.autotune_events)} shape keys "
              f"resolved at warmup")
        for ev in eng2.autotune_events:
            print(f"  {ev.name.split(':', 1)[1]}")
        streams2 = eng2.run(reqs)
        assert streams2 == streams, \
            "autotuned engine streams diverge from untuned run"
        print("autotuned streams byte-identical to untuned run ✓")
    failed = [s for s in eng.sequences if s.status is Status.FAILED]
    if failed and args.deadline_ticks is None:
        print(f"{len(failed)} of {len(reqs)} requests FAILED",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
