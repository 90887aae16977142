"""Run one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  One process per run:

1. JAX must have come up on an accelerator whose ``device_kind`` is in
   ``roofline/peaks.json``, with as many devices as the cell asks for;
   otherwise the run exits 2 and prints no result.
2. The program's compile cache is switched on (``use_compile_cache``).
3. Set-up: the weights are drawn on the device from the seed, the
   ``ServeEngine`` is built from the configuration's file and every shape
   the cell's traffic uses is compiled and run once.
4. The window: the cell's traffic for ``--seconds`` (open loop: then a
   drain until every request due in the window has its first token).
5. The correctness check against the plain reference, once the engine
   and its weights are freed.
6. The last line of standard output is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
   also ``busy_s``, ``window_s`` and a ``breakdown``), and ``checks``, the
   numbers compared with their limits, which also end standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the run is traced with the JAX profiler and the metrics are
the cell's per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

from lib import spec  # noqa: E402

OUT = BENCH / "out"
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark has peaks for, or too few."""


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or d.device_kind not in spec.peaks() \
            or len(devs) < chips:
        raise NoChip(f"JAX came up on {len(devs)} {d.platform!r} device(s) "
                     f"of kind {d.device_kind!r}; this cell needs {chips} "
                     f"of a kind in roofline/peaks.json "
                     f"({sorted(spec.peaks())})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def program_config(cell: spec.Cell):
    """The program's model configuration and the sizes the reference
    uses, once the reference has found the one true to the other."""
    from repro.configs import get_config

    m = cell.config["model"]
    cfg = get_config(m["arch"], **m.get("overrides", {}))
    return cfg, cell.reference().program_dims(cfg, cell.config)


def warm(eng, plan, Request, opts: dict, seed: int, annotate) -> int:
    """Compile and run once every shape the plan's traffic uses, through
    ``submit`` and ``step``: every decode width (as many requests as
    slots, finishing one per step), one prompt per length bucket the
    plan's prompts reach, and, where prompts share pages, every pair of
    shared-prefix bucket and tail bucket a prompt within the budget can
    reach.  Returns the number of warm-up requests."""
    import numpy as np
    from repro.serve.step import length_ladder

    budget, ps = opts["budget"], opts["page_size"]
    ladder = length_ladder(ps, budget)
    rng = np.random.default_rng(seed ^ 0x5EED)
    vocab = eng.cfg.vocab

    def prompt(n):
        return rng.integers(0, vocab, n)

    longest = max(len(plan.first_prompt(s)) for s in plan.sessions)
    if any(len(s.turns) > 1 for s in plan.sessions):
        longest = budget
    top = next(b for b in ladder if b >= min(longest, budget - 2))
    groups = [[(prompt(ps), i + 2) for i in range(eng.n_slots)]]
    groups += [[(prompt(min(b, budget - 2)), 2)] for b in ladder
               if b <= top]
    if opts.get("prefix_sharing") and plan.system_prompts:
        m = 1
        while m * ps < budget - 2:
            base = prompt(m * ps)
            prev = 0
            for b in ladder:
                tail = min(b, budget - 2 - m * ps)
                if tail > prev:
                    groups.append([
                        (np.concatenate([base, prompt(1)]), 2),
                        (np.concatenate([base, prompt(tail)]), 2)])
                prev = b
            m *= 2
    n = 0
    with annotate("bench.warmup"):
        for g in groups:
            for toks, new in g:
                n += 1
                eng.submit(Request(10**9 + n,
                                   toks.astype(np.int32).tolist(), new))
            while not eng.done:
                eng.step()
        eng.finish()
    return n


def build(cell: spec.Cell, seed: int, annotate):
    """The program's configuration, the seeded weights on the device and
    the engine, with a greedy ``sample_fn`` that marks its host time."""
    import jax
    import numpy as np
    from lib.weights import make_program_params
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    cfg, dims = program_config(cell)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = make_program_params(shapes, seed, cell.reference().LEAVES)
    jax.block_until_ready(params)

    def sample_fn(lg):
        with annotate("bench.sample"):
            return np.argmax(lg, axis=-1)

    eng = ServeEngine(cfg, params, sample_fn=sample_fn,
                      **cell.config["engine"])
    return cfg, dims, params, eng


def engine_counters(eng) -> dict:
    """Every counter of the engine's metrics registry, by name.  A run's
    ``counters`` are their change over the window, so a counter the
    engine gains reaches the metric readers with no entry here.  Gauges
    (levels such as ``queue_depth``) and histograms are left out: their
    change is no count.  The registry names its kinds only in private
    maps, so the counters' map is read directly."""
    return {name: c.value for name, c in eng.metrics._counters.items()}


def use_cache() -> str:
    """The program's compile cache, keeping every program it compiles."""
    import jax
    from repro.core.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             log=print):
    """One run of ``cell``.  Returns the result object and the sequences
    the check compared: ``(prompt + served tokens, first served index)``
    each."""
    import jax

    dev = device_info(cell.chips)
    peak = spec.peaks().get(dev["kind"])
    cache_dir = use_cache()

    from lib import check, devtrace, traffic
    from lib.driver import Driver
    from lib.measure import Run, p
    from repro.prof.trace import SpanKind
    from repro.serve.engine import Request

    annotate = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    config, opts = cell.config, dict(cell.config["engine"])
    log(f"device {dev}; compile cache {cache_dir}")
    cfg, dims, params, eng = build(cell, seed, annotate)
    log(f"engine {json.dumps(opts)} attn_impl={cfg.attn_impl}")
    plan = traffic.make_plan(cell.traffic, cell.load, seed=seed,
                             seconds=seconds, vocab=cfg.vocab,
                             budget=opts["budget"], bench_dir=cell.bench_dir)
    log(f"plan {json.dumps(traffic.summary(plan))}")
    open_loop = plan.open_loop
    n_warm = warm(eng, plan, Request, opts, seed, annotate)
    drv = Driver(eng, plan, request_cls=Request, annotate=annotate)
    if not open_loop:
        drv.fill()

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if ev.startswith("/jax/core/compile") else None)
    before = engine_counters(eng)
    n_compiles = len(compiles)
    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts_t = jax.profiler.ProfileOptions()
        opts_t.python_tracer_level = 0     # the benchmark's spans only
        opts_t.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts_t)
    setup_s = time.perf_counter() - T_START
    with annotate(WINDOW_SPAN):
        close = drv.run_window(seconds, open_loop)
    counters = {k: v - before[k] for k, v in engine_counters(eng).items()}
    in_window = len(compiles) - n_compiles
    drain_end = drv.drain(close) if open_loop else close
    eng.finish()
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    waits = []
    for r in drv.records:
        if r.seq is not None and r.due < close and eng.trace is not None:
            q = [s for s in eng.trace.traces[r.rid].spans
                 if s.kind is SpanKind.QUEUED and s.t1 is not None]
            waits += [(s.t1 - s.t0) / 1e6 for s in q]
    run = Run(dims=dims, peak=peak, records=drv.records, close=close,
              open_loop=open_loop, counters=counters, queue_waits_ms=waits,
              drain_end=drain_end)
    late = [r.submit - r.due for r in drv.records
            if r.seq is not None and r.due < close]
    due = run.due_in_window()
    failed = [r for r in due if r.status == "failed" or r.first is None]
    log(f"set-up: {n_warm} warm-up requests, setup_s {setup_s}")
    log(f"window: {close} s, {len(due)} requests due, "
        f"{sum(r.status == 'finished' for r in due)} finished, "
        f"{len(failed)} failed or unanswered, {drv.steps} steps, "
        f"{run.tokens_in_window()} tokens; drain ended at {drain_end} s")
    log(f"generator lateness s: p50 {p(late, 50)} p95 {p(late, 95)} "
        f"max {max(late) if late else None}")
    log(f"counters over the window {json.dumps(counters)}; "
        f"compiles inside the window {in_window}")

    metrics = {}
    if trace:
        events = devtrace.load_xplane(str(trace_dir))
        run.window = devtrace.window(events, WINDOW_SPAN)
        summ = devtrace.summary(run.window)
        dev["busy_s"], dev["window_s"] = summ["busy_s"], summ["window_s"]
        for m in cell.per_layer:
            v = spec.metric_reader(cell.bench_dir, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"ttft_p95_ms": lambda: p(run.ttfts_ms(), 95),
               "ttft_p50_ms": lambda: p(run.ttfts_ms(), 50),
               "itl_p95_ms": lambda: p(run.itls_ms(), 95),
               "output_tokens_per_s":
                   lambda: run.tokens_in_window() / close,
               "setup_s": lambda: setup_s}
        for m in cell.end_to_end:
            v = e2e[m["name"]]()
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    picked = check.sample(drv.records, seed)
    seqs = check.sequences(picked)
    n_failed_status = sum(r.status == "failed" for r in drv.records)
    del eng, params, drv, run
    gc.collect()
    t_ref = time.perf_counter()
    read = check.numbers(cell.reference(), config, seed, seqs)
    log(f"reference: {len(seqs)} requests, "
        f"{sum(len(t) - s for t, s in seqs)} served tokens, "
        f"{time.perf_counter() - t_ref} s; {json.dumps(read)}")
    checks = {k: {"value": read[k], "limit": lim}
              for k, lim in cell.load["limits"].items()}
    checks["failed_requests"] = {"value": n_failed_status, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": len(due),
           "failed": len(failed), "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = summ["breakdown"]
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    return out, seqs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        out, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
