"""Benchmark harness — one entry per paper table/figure + the roofline
table.  Prints ``name,us_per_call,derived`` CSV rows (plus the full
pretty-printed reports to stderr).

  E1 loc_compare   — paper §6.1 (LOC table): raw-JAX vs framework app
  E2 overhead      — paper Fig. 4: overhead grid over (n, i)
  E3 prof_summary  — paper Fig. 3: aggregate events + overlaps
  E4 queue_chart   — paper Fig. 5: queue utilization chart
  E5 prng_quality  — dieharder-lite statistical checks
  E6 roofline      — per-(arch × shape) roofline terms from the dry-run
  E7 decode_throughput — tokens/s vs cache length, XLA vs fused Pallas
                     decode path (→ BENCH_decode.json perf trajectory)
  E8 serve_throughput — continuous batching vs lockstep under a Poisson
                     arrival trace (→ BENCH_serve.json)
  E9 paged_vs_dense — paged KV pool vs dense per-slot rings: tokens/s +
                     resident KV bytes at equal traffic (→ BENCH_serve.json
                     "paged_vs_dense")
  E10 prefix_sharing — sharing on vs off over identical traces, two
                     scenarios: a preemption-contended pool (gates
                     sharing_speedup ≥ 1.0) and agentic fan-out over
                     decode-produced pages; streams bit-identical
                     (→ BENCH_serve.json "prefix_sharing")

The ``BENCH_*.json`` files are *snapshots* (overwritten per run); every
perf bench additionally appends a ``{git_rev, timestamp}``-stamped row to
``BENCH_history.jsonl``.  The history file is committed, so the
trajectory accrues in-repo as PRs re-run the benches; CI uploads the
refreshed copy (committed rows + that run's rows) as an artifact.

Run:  PYTHONPATH=src python -m benchmarks.run [names...]
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
HISTORY = ROOT / "BENCH_history.jsonl"


def _emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.3f},{derived}")


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:   # noqa: BLE001 — no git / not a checkout
        return "unknown"


def _history_append(bench: str, summary: dict) -> None:
    """Append one stamped row to BENCH_history.jsonl (the snapshot files
    are overwritten per run; this is the trajectory that survives)."""
    row = {"bench": bench, "git_rev": _git_rev(),
           "timestamp": time.time(), **summary}
    with HISTORY.open("a") as f:
        f.write(json.dumps(row) + "\n")
    print(f"# appended {bench} row to {HISTORY}", file=sys.stderr)


def _merge_snapshot(path: pathlib.Path, update: dict) -> None:
    """Merge ``update`` into a snapshot JSON (benches that share a file —
    E8/E9 both land in BENCH_serve.json — must not clobber each other
    when run individually)."""
    data = {}
    if path.exists():
        try:
            data = json.loads(path.read_text())
        except json.JSONDecodeError:
            data = {}
    data.update(update)
    path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"# wrote {path}", file=sys.stderr)


def _latency_cols(eng) -> dict:
    """p50/p99 TTFT and inter-token latency (engine ticks — deterministic
    across backends) from a served engine's metrics registry; appended
    to every serve scenario row."""
    st = eng.stats
    return {"ttft_ticks_p50": st.percentile("ttft_ticks", 50),
            "ttft_ticks_p99": st.percentile("ttft_ticks", 99),
            "tbt_ticks_p50": st.percentile("tbt_ticks", 50),
            "tbt_ticks_p99": st.percentile("tbt_ticks", 99)}


# ----------------------------------------------------------------- E1 ------

def bench_loc_compare():
    def loc(path):
        n = 0
        in_doc = False
        for line in pathlib.Path(path).read_text().splitlines():
            s = line.strip()
            if not s:
                continue
            if in_doc:
                if s.endswith('"""') or s.endswith("'''"):
                    in_doc = False
                continue
            if s.startswith('"""') or s.startswith("'''"):
                if not (len(s) > 3 and (s.endswith('"""') or
                                        s.endswith("'''"))):
                    in_doc = True
                continue
            if s.startswith("#"):
                continue
            n += 1
        return n

    raw = loc(ROOT / "benchmarks" / "rng_raw.py")
    fw = loc(ROOT / "examples" / "rng_stream.py")
    print(f"# LOC: raw-jax implementation = {raw}, framework = {fw} "
          f"({100 * (raw - fw) / raw:.0f}% smaller; paper: 290 vs 183 = 37%)",
          file=sys.stderr)
    _emit("loc_compare_raw", raw, "physical LOC")
    _emit("loc_compare_framework", fw,
          f"{100 * (raw - fw) / raw:.0f}% smaller")


# ----------------------------------------------------------------- E2 ------

def bench_overhead():
    from benchmarks import rng_framework, rng_raw
    print("# overhead grid (paper Fig. 4): t_framework / t_raw",
          file=sys.stderr)
    grid_n = [1 << 12, 1 << 15, 1 << 18]
    grid_i = [4, 16]
    for n in grid_n:
        for i in grid_i:
            # warmup both (jit compile out of the timing)
            rng_raw.run(n, 2)
            rng_framework.run(n, 2)
            reps = 3
            t_raw = min(rng_raw.run(n, i)["total_s"] for _ in range(reps))
            t_fw = min(rng_framework.run(n, i)[0]["total_s"]
                       for _ in range(reps))
            ratio = t_fw / t_raw
            print(f"#   n=2^{n.bit_length() - 1} i={i}: raw={t_raw:.4f}s "
                  f"fw={t_fw:.4f}s overhead={100 * (ratio - 1):+.1f}%",
                  file=sys.stderr)
            _emit(f"overhead_n{n.bit_length() - 1}_i{i}", t_fw * 1e6,
                  f"ratio={ratio:.3f}")


# ----------------------------------------------------------------- E3/E4 ---

def bench_prof_summary():
    from benchmarks import rng_framework
    t0 = time.perf_counter()
    stats, prof = rng_framework.run(1 << 16, 12)
    us = (time.perf_counter() - t0) * 1e6
    print(prof.get_summary(), file=sys.stderr)
    _emit("prof_summary", us,
          f"overlap_s={stats['overlap_s']:.4f}")
    return prof


def bench_queue_chart(prof=None):
    from repro.prof import queue_chart
    if prof is None:
        prof = bench_prof_summary()
    t0 = time.perf_counter()
    chart = queue_chart(prof, width=90)
    us = (time.perf_counter() - t0) * 1e6
    print(chart, file=sys.stderr)
    _emit("queue_chart", us, f"{len(chart.splitlines())} lines")


# ----------------------------------------------------------------- E5 ------

def bench_prng_quality():
    import numpy as np
    from repro.kernels.xorshift_prng import ops
    t0 = time.perf_counter()
    s = ops.prng_init(1 << 16, block_rows=64)
    for _ in range(3):
        s = ops.prng_step(s, block_rows=64)
    vals = ops.to_uint64(s)
    bits = np.unpackbits(vals.view(np.uint8))
    z = abs(bits.sum() - bits.size / 2) / (bits.size / 4) ** 0.5
    counts = np.bincount(vals.view(np.uint8), minlength=256)
    chi2 = (((counts - counts.mean()) ** 2) / counts.mean()).sum()
    us = (time.perf_counter() - t0) * 1e6
    print(f"# prng quality: monobit z={z:.3f} (<4), byte chi2={chi2:.1f} "
          f"(~255±45)", file=sys.stderr)
    _emit("prng_monobit_z", us, f"z={z:.3f}")
    _emit("prng_byte_chi2", us, f"chi2={chi2:.1f}")


# ----------------------------------------------------------------- E6 ------

def bench_roofline():
    ddir = ROOT / "experiments" / "dryrun"
    rows = []
    for f in sorted(ddir.glob("*__baseline.json")):
        d = json.loads(f.read_text())
        rows.append((d, d["roofline"]))
    if not rows:
        print("# (no dry-run results yet — run repro.launch.dryrun --all)",
              file=sys.stderr)
        return
    print("# roofline table (per-device terms, v5e constants):",
          file=sys.stderr)
    for d, r in rows:
        print(f"#  {r['arch']:26s} {r['shape']:12s} {r['mesh']:8s} "
              f"c={r['compute_s']:9.4f} m={r['memory_s']:9.4f} "
              f"x={r['collective_s']:9.4f} dom={r['dominant']:10s} "
              f"useful={r['useful_ratio']:.3f} "
              f"fits={'Y' if r['fits_hbm'] else 'N'}", file=sys.stderr)
        _emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
              r["bound_s"] * 1e6,
              f"dom={r['dominant']};frac={r['roofline_fraction']:.4f}")


# ----------------------------------------------------------------- E7 ------

def bench_decode_throughput():
    """Single-layer fused decode op: autotune sweep over the candidate
    grids per cache length.  Every candidate — the XLA reference is one
    of them, EngineCL-style — is timed with one discipline; the winner
    is persisted to the autotune cache (``.autotune_cache.json``, the
    measured tier the serve engine's ``impl="auto"`` resolves from, and
    a CI artifact).  ``pallas_tok_s`` reports the *autotuned path*: the
    per-shape winner the one numeric path actually runs.  On CPU the
    Pallas grids run in interpret mode — orders of magnitude slower by
    construction — so there the sweep doubles as the correctness gate
    (every grid must agree with the reference) and the reference
    candidate wins; on TPU the same harness makes the fused grids
    compete on merit.  Results land in BENCH_decode.json (sweep rows +
    chosen config per cache length) so future PRs have a trajectory to
    regress against.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.autotune import Autotuner, ShapeKey
    from repro.kernels.decode_attention.ops import decode_attention

    interpret = jax.default_backend() == "cpu"
    B, Hq, Hkv, D = 4, 8, 2, 64
    steps = 8
    key = jax.random.PRNGKey(0)
    tuner = Autotuner(path=str(ROOT / ".autotune_cache.json"))
    results = {"backend": jax.default_backend(), "interpret": interpret,
               "shape": {"batch": B, "q_heads": Hq, "kv_heads": Hkv,
                         "head_dim": D}, "rows": []}

    def run(impl, S, reps, block_kv=0):
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, Hq, 1, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        kn = jax.random.normal(ks[3], (B, Hkv, 1, D), jnp.float32)
        vn = jax.random.normal(ks[4], (B, Hkv, 1, D), jnp.float32)
        half = jnp.where(jnp.arange(S)[None] < S // 2,
                         jnp.arange(S)[None], -1)
        pc = jnp.broadcast_to(half, (B, S)).astype(jnp.int32)
        kw = {"block_kv": block_kv} if block_kv else {}

        def one_pass():
            out, ck, cv, cp = None, kc, vc, pc
            for t in range(steps):
                out, ck, cv, cp = decode_attention(
                    q, ck, cv, cp, kn, vn, jnp.int32(S // 2 + t),
                    impl=impl, **kw)
            return jax.block_until_ready(out)

        out = one_pass()                       # warmup (compile)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = one_pass()
        dt = (time.perf_counter() - t0) / reps
        return B * steps / dt, dt, out

    cache_lens = [256, 1024, 4096] if not interpret else [64, 256]
    for S in cache_lens:
        reps = 3 if not interpret else 1
        skey = ShapeKey("decode", cache_len=S, q_len=1, q_heads=Hq,
                        kv_heads=Hkv, head_dim=D, page_size=0,
                        dtype="float32", backend=jax.default_backend())
        cands = tuner.candidates(skey)
        if interpret:
            # interpret-mode grids cost seconds each: keep the extreme
            # split counts (max-split and single-split) and say so
            grids = [c for c in cands if c.impl == "pallas"]
            keep = {grids[0], grids[-1]}
            dropped = [c.block_kv for c in grids if c not in keep]
            if dropped:
                print(f"# decode S={S}: interpret mode — skipping pallas "
                      f"grids block_kv={dropped}", file=sys.stderr)
            cands = [c for c in cands if c.impl == "xla" or c in keep]
        sweep, out_x, timed = [], None, []
        for cand in cands:
            tok, dt, out = run(cand.impl, S, reps, cand.block_kv)
            if cand.impl == "xla":
                out_x = out
            sweep.append({"impl": cand.impl, "block_kv": cand.block_kv,
                          "tok_s": tok, "us_per_step": dt / steps * 1e6})
            timed.append((tok, cand, out))
        for (tok, cand, out), row in zip(timed, sweep):
            if cand.impl == "xla":
                row["max_abs_err"] = 0.0
                continue
            err = float(np.max(np.abs(np.asarray(out_x, np.float32) -
                                      np.asarray(out, np.float32))))
            row["max_abs_err"] = err
            assert err < 1e-3, \
                f"decode grid {cand} diverges at S={S}: {err}"
        tok_x = next(r["tok_s"] for r in sweep if r["impl"] == "xla")
        best_tok, best, _ = max(timed, key=lambda t: t[0])
        tuner.record(skey, best, sweep=sweep, source="measured")
        row = {"cache_len": S, "xla_tok_s": tok_x, "pallas_tok_s": best_tok,
               "tuned_impl": best.impl, "chosen": best.to_json(),
               "sweep": sweep,
               "max_abs_err": max(r["max_abs_err"] for r in sweep)}
        results["rows"].append(row)
        print(f"# decode S={S}: xla={tok_x:,.1f} tok/s "
              f"tuned={best_tok:,.1f} tok/s via {best.to_json()} "
              f"({'interpret' if interpret else 'native'})",
              file=sys.stderr)
        _emit(f"decode_throughput_S{S}_xla",
              next(r["us_per_step"] for r in sweep if r["impl"] == "xla"),
              f"tok_s={tok_x:.1f}")
        _emit(f"decode_throughput_S{S}_tuned", 1e6 / best_tok * B,
              f"tok_s={best_tok:.1f},impl={best.impl},"
              f"block_kv={best.block_kv}")
    results["pallas_ge_xla"] = all(
        r["pallas_tok_s"] >= r["xla_tok_s"] for r in results["rows"])
    results["autotune_cache"] = tuner.path
    _merge_snapshot(ROOT / "BENCH_decode.json", results)
    _history_append("decode_throughput", {
        "backend": results["backend"], "rows": results["rows"],
        "pallas_ge_xla": results["pallas_ge_xla"]})


# ----------------------------------------------------------------- E8 ------

def bench_serve_throughput():
    """Continuous batching vs static (batch-synchronous) batching under a
    Poisson arrival trace.

    Both paths serve the same seeded trace with the same greedy decoding
    and the same per-sequence-position decode step; what differs is the
    *scheduling policy*: the static baseline admits a full batch at once
    and decodes until its slowest member finishes (finished slots keep
    burning decode work, late batches wait for stragglers), while the
    engine admits into any freed slot every tick.  Idle waiting is free
    in both simulations (arrivals are tick-indexed), so the gap measured
    here — wasted decode-slot work — is the conservative lower bound of
    the continuous-batching win.  Results land in BENCH_serve.json.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import ModelConfig, init_params
    from repro.serve.engine import BatchedCacheManager, Request, ServeEngine
    from repro.serve.step import (align_prefill_cache, make_decode_step,
                                  make_prefill_step)

    cfg = ModelConfig(name="bench-serve", family="dense", num_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab=256, dtype="float32")
    n_slots, budget = 4, 48
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    rng = np.random.default_rng(42)
    arrivals = np.cumsum(rng.poisson(1.5, size=16))
    reqs = [Request(i, [int(t) for t in rng.integers(0, cfg.vocab,
                                                     rng.integers(4, 13))],
                    int(rng.integers(4, 17)), arrival=int(a))
            for i, a in enumerate(arrivals)]

    def run_continuous():
        eng = ServeEngine(cfg, params, n_slots=n_slots, budget=budget)
        streams = eng.run(reqs)
        return streams, eng.stats["decode_steps"], eng

    def run_static():
        prefill = make_prefill_step(cfg)
        decode = make_decode_step(cfg)
        streams, steps = {}, 0
        for base in range(0, len(reqs), n_slots):
            group = reqs[base: base + n_slots]
            mgr = BatchedCacheManager(cfg, n_slots, budget)
            toks = np.zeros((n_slots, 1), np.int32)
            pos = np.full((n_slots,), -1, np.int32)
            for slot, r in enumerate(group):
                logits, cache = prefill(params,
                                        jnp.asarray(r.prompt,
                                                    jnp.int32)[None, :])
                cache = align_prefill_cache(cfg, cache, len(r.prompt),
                                            target_len=budget)
                mgr.insert(cache, slot)
                streams[r.rid] = [int(np.argmax(np.asarray(logits[0, -1])))]
                toks[slot, 0] = streams[r.rid][0]
                pos[slot] = len(r.prompt)
            # lockstep: the whole batch decodes until its slowest member
            # is done; finished members keep occupying their slots
            for _ in range(max(r.max_new_tokens for r in group) - 1):
                logits, cache = decode(params, mgr.cache,
                                       jnp.asarray(toks), jnp.asarray(pos))
                mgr.update(cache)
                steps += 1
                nxt = np.argmax(np.asarray(logits[:, 0]), -1)
                for slot, r in enumerate(group):
                    if len(streams[r.rid]) < r.max_new_tokens:
                        streams[r.rid].append(int(nxt[slot]))
                    toks[slot, 0] = int(nxt[slot])
                    pos[slot] += 1
        return streams, steps, None

    results = {"backend": jax.default_backend(),
               "trace": {"n_requests": len(reqs), "n_slots": n_slots,
                         "budget": budget, "poisson_mean_gap": 1.5},
               "rows": []}
    for name, fn in [("lockstep", run_static),
                     ("continuous", run_continuous)]:
        fn()                                   # warmup (jit compile)
        t0 = time.perf_counter()
        streams, steps, eng = fn()
        dt = time.perf_counter() - t0
        toks = sum(len(s) for s in streams.values())
        decoded = toks - len(reqs)             # first token is prefill's
        util = decoded / max(1, steps * n_slots)
        row = {"policy": name, "tokens": toks, "decode_steps": steps,
               "tok_s": toks / dt, "slot_utilization": util, "wall_s": dt}
        if eng is not None:         # lockstep baseline has no engine
            row.update(_latency_cols(eng))
        results["rows"].append(row)
        results[f"streams_{name}"] = {str(k): v
                                      for k, v in sorted(streams.items())}
        print(f"# serve {name}: {toks} tokens in {dt:.3f}s "
              f"({toks / dt:,.1f} tok/s), {steps} decode steps, "
              f"slot util {util:.2f}", file=sys.stderr)
        _emit(f"serve_throughput_{name}", dt * 1e6,
              f"tok_s={toks / dt:.1f};util={util:.2f}")
    results["streams_match"] = (results.pop("streams_lockstep") ==
                                results.pop("streams_continuous"))
    cont, lock = results["rows"][1], results["rows"][0]
    results["fewer_steps_continuous"] = \
        cont["decode_steps"] <= lock["decode_steps"]
    print(f"# streams_match={results['streams_match']} "
          f"steps: lockstep={lock['decode_steps']} "
          f"continuous={cont['decode_steps']}", file=sys.stderr)
    _merge_snapshot(ROOT / "BENCH_serve.json", results)
    _history_append("serve_throughput", {
        "backend": results["backend"], "rows": results["rows"],
        "streams_match": results["streams_match"]})


# ----------------------------------------------------------------- E9 ------

def bench_paged_vs_dense():
    """Paged KV pool vs dense per-slot rings at equal traffic.

    The same mixed-length Poisson trace is served twice by the engine —
    once on the dense standing cache (every slot pinned at the budget),
    once on the paged pool with the arena capped well below the dense
    provision — with identical greedy decoding.  The paged run must
    produce byte-identical streams (preempting and swapping if the pool
    runs dry); what changes is *resident KV bytes*: the dense cache pins
    ``n_slots × W`` positions for the whole run, the pool pins only its
    arena, and actually-used pages track live sequence lengths.  Results
    land under the ``paged_vs_dense`` key of BENCH_serve.json.
    """
    import jax
    import numpy as np
    from repro.models import model as Mmod
    from repro.models.model import ModelConfig, init_params
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.paging import kv_resident_bytes

    cfg = ModelConfig(name="bench-paged", family="dense", num_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab=256, dtype="float32")
    n_slots, budget, page_size, pool_pages = 4, 48, 4, 20
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    # mixed-length trace: short chats next to near-budget prompts — the
    # length diversity dense allocation cannot exploit
    rng = np.random.default_rng(11)
    arrivals = np.cumsum(rng.poisson(1.5, size=16))
    reqs = []
    for i, a in enumerate(arrivals):
        L = int(rng.integers(4, 33))
        n = int(rng.integers(4, min(17, budget - L + 1)))
        reqs.append(Request(i, [int(t) for t in rng.integers(0, cfg.vocab,
                                                             L)],
                            n, arrival=int(a)))

    def serve(paged):
        kw = dict(paged=True, page_size=page_size,
                  pool_pages=pool_pages) if paged else {}
        eng = ServeEngine(cfg, params, n_slots=n_slots, budget=budget,
                          **kw)
        # ServeEngine.run with per-tick page sampling bolted on — keep
        # run()'s non-convergence guard so a scheduling livelock fails
        # the bench instead of hanging CI
        pending = sorted(reqs, key=lambda r: (r.arrival, r.rid))
        i, peak_pages = 0, 0
        while i < len(pending) or not eng.done:
            if eng.tick > 10_000:
                raise RuntimeError("serve trace did not converge")
            while i < len(pending) and pending[i].arrival <= eng.tick:
                eng.submit(pending[i])
                i += 1
            eng.step()
            if paged:
                peak_pages = max(peak_pages,
                                 sum(eng.cache_mgr.pages_held().values()))
        eng.finish()
        streams = {s.rid: list(s.out_tokens) for s in eng.sequences}
        return eng, streams, peak_pages

    out = {"trace": {"n_requests": len(reqs), "n_slots": n_slots,
                     "budget": budget, "page_size": page_size,
                     "pool_pages": pool_pages},
           "rows": []}
    streams_by = {}
    for name, paged in [("dense", False), ("paged", True)]:
        serve(paged)                           # warmup (jit compile)
        t0 = time.perf_counter()
        eng, streams, peak_pages = serve(paged)
        dt = time.perf_counter() - t0
        toks = sum(len(s) for s in streams.values())
        resident = (eng.cache_mgr.resident_bytes() if paged
                    else kv_resident_bytes(eng.cache_mgr.cache))
        row = {"layout": name, "tokens": toks, "tok_s": toks / dt,
               "decode_steps": eng.stats["decode_steps"],
               "resident_kv_bytes": resident, "wall_s": dt,
               "preemptions": eng.stats["preemptions"]}
        row.update(_latency_cols(eng))
        if paged:
            row["peak_pages_held"] = peak_pages
        out["rows"].append(row)
        streams_by[name] = streams
        print(f"# {name}: {toks} tokens in {dt:.3f}s ({toks / dt:,.1f} "
              f"tok/s), resident KV {resident:,} B"
              + (f", peak pages {peak_pages}, "
                 f"{eng.stats['preemptions']} preemptions" if paged
                 else ""), file=sys.stderr)
        _emit(f"paged_vs_dense_{name}", dt * 1e6,
              f"tok_s={toks / dt:.1f};kv_bytes={resident}")
    dense_row, paged_row = out["rows"]
    out["streams_match"] = streams_by["dense"] == streams_by["paged"]
    out["kv_bytes_ratio"] = (dense_row["resident_kv_bytes"] /
                             paged_row["resident_kv_bytes"])
    print(f"# streams_match={out['streams_match']} resident-KV ratio "
          f"dense/paged = {out['kv_bytes_ratio']:.2f}x", file=sys.stderr)
    assert out["streams_match"], "paged serving diverged from dense!"
    _merge_snapshot(ROOT / "BENCH_serve.json", {"paged_vs_dense": out})
    _history_append("paged_vs_dense", {
        "rows": out["rows"], "streams_match": out["streams_match"],
        "kv_bytes_ratio": out["kv_bytes_ratio"]})


# ----------------------------------------------------------------- E10 -----

def bench_prefix_sharing():
    """Prefix sharing + copy-on-write vs the unshared paged pool.

    Two scenarios, each served twice over an identical trace with
    identical greedy decoding — ``prefix_sharing=False`` (the plain
    paged baseline) and ``True``:

    * **contended** — N sequences over one *long* (32-token) system
      prompt, dense config, on a pool capped well below the fleet's
      unshared footprint.  Unshared serving can barely keep one
      sequence's pages resident, so it serializes; the shared run
      over-admits on the same cap and rides preempt → resume cycles.
      This is where sharing used to *lose* throughput (E10's 614 vs
      708 tok/s): victims were picked by age alone (often evicting a
      mostly-shared sequence that freed ~nothing) and every preempt →
      resume cycle re-duplicated the shared prefix into fresh exclusive
      pages.  With exclusive-page-weighted victims, prefix pinning and
      swap-in re-match (the shared row's ``resume_shared_tokens``
      counts prefix tokens restored *by reference* across those
      cycles), sharing must win — it prefills a fraction of the tokens
      and preemption no longer costs it the prefix:
      ``sharing_speedup`` (shared over unshared tok/s, best-of-reps
      against CPU noise) is asserted ≥ 1.0 — CI runs this bench, so
      the regression cannot silently return.
    * **fanout** — one seed request plus continuations that extend the
      seed's prompt *and its output* (agentic fan-out).  Decode-produced
      pages are registered as they close, so continuations share past
      the prompt: the row's shared-token count exceeds what prompt-only
      sharing could ever reach, and peak resident pages shrink.

    Sharing must keep the streams byte-identical in both scenarios
    (divergent sequences copy-on-write before their first conflicting
    ring write).  Results land under the ``prefix_sharing`` key of
    BENCH_serve.json; the legacy top-level ratios are the contended
    scenario's.
    """
    import jax
    import numpy as np
    from repro.models.model import ModelConfig, init_params
    from repro.serve.engine import Request, ServeEngine

    dims = dict(family="dense", num_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                dtype="float32")
    # contended: dense/full attention so the shareable prefix can be
    # long (a swa ring caps sharing at its window) — the 32-token
    # system prompt is 8 shared pages, most of each request's footprint
    cfg_dense = ModelConfig(name="bench-prefix-dense", **dims)
    # fanout: hybrid swa+full — the swa ring (window < budget) wraps
    # back into the shared pages mid-decode, so the scenario exercises
    # copy-on-write, not just read sharing
    cfg_hyb = ModelConfig(name="bench-prefix", **dims,
                          pattern=(("swa", "dense"), ("full", "dense")),
                          window=16)
    n_slots, budget, page_size = 4, 48, 4
    n_seqs, sys_len = 8, 32                     # 8 shared pages
    # every kind capped at 16 pages: one unshared active needs 10-12
    # full pages, so the unshared baseline degrades to near-serial
    # admission, while the shared fleet (8 prefix pages resident once +
    # small exclusive tails) packs several actives into the same cap
    # and absorbs the resulting preemptions via pin + swap-in re-match
    pool_cap = 16
    key = jax.random.PRNGKey(0)
    params_dense = init_params(cfg_dense, key)
    params_hyb = init_params(cfg_hyb, key)

    rng = np.random.default_rng(23)
    system = [int(t) for t in rng.integers(0, cfg_dense.vocab, sys_len)]
    reqs = []
    for i in range(n_seqs):
        tail = [int(t) for t in rng.integers(0, cfg_dense.vocab,
                                             rng.integers(2, 7))]
        reqs.append(Request(i, system + tail, int(rng.integers(6, 13)),
                            arrival=int(i // 2)))

    def serve(cfg, params, trace, sharing, pool_pages):
        eng = ServeEngine(cfg, params, n_slots=n_slots, budget=budget,
                          paged=True, page_size=page_size,
                          prefix_sharing=sharing, pool_pages=pool_pages)
        pending = sorted(trace, key=lambda r: (r.arrival, r.rid))
        i, peak_pages = 0, 0
        while i < len(pending) or not eng.done:
            if eng.tick > 10_000:
                raise RuntimeError("serve trace did not converge")
            while i < len(pending) and pending[i].arrival <= eng.tick:
                eng.submit(pending[i])
                i += 1
            eng.step()
            peak_pages = max(peak_pages,
                             sum(eng.cache_mgr.pages_held().values()))
        eng.finish()
        streams = {s.rid: list(s.out_tokens) for s in eng.sequences}
        return eng, streams, peak_pages

    # the fan-out continuations extend the seed's prompt AND output, so
    # the stem needs the seed's greedy stream (any serve of the seed is
    # bit-identical to this one — that is the conformance contract).
    # stem = prompt + one closed decode page; the continuation prompt
    # must stay ≤ the swa window (a wrapped ring cannot share), and the
    # continuations must land after the seed's decode page closes
    # (tick 3) but before its swa ring wraps back over the prefix
    # (tick 8) — inside that window the shared pages CoW instead of
    # being rewritten in place, so registrations survive
    fan_sys = system[:8]                        # 2 pages at the window
    seed = Request(0, fan_sys, 16, arrival=0)
    _, seed_streams, _ = serve(cfg_hyb, params_hyb, [seed], False, None)
    stem = fan_sys + seed_streams[0][:4]        # 3 pages, 1 decode-made
    fan_reqs = [seed] + [
        Request(1 + i, stem + [int(t) for t in
                               rng.integers(0, cfg_hyb.vocab, 2)],
                10, arrival=5)
        for i in range(4)]

    scenarios = [("contended", cfg_dense, params_dense, reqs, pool_cap),
                 ("fanout", cfg_hyb, params_hyb, fan_reqs, None)]
    out = {"trace": {"n_requests": len(reqs), "n_slots": n_slots,
                     "budget": budget, "page_size": page_size,
                     "pool_pages": pool_cap,
                     "system_prompt_tokens": sys_len,
                     "shared_pages_per_seq": sys_len // page_size,
                     "fanout_continuations": len(fan_reqs) - 1},
           "scenarios": {}}
    reps = 3
    for scen, cfg, params, trace, cap in scenarios:
        rows, streams_by = [], {}
        for name, sharing in [("unshared", False), ("shared", True)]:
            serve(cfg, params, trace, sharing, cap)   # warmup (jit)
            best = None
            for _ in range(reps):               # best-of-reps: CPU noise
                t0 = time.perf_counter()
                eng, streams, peak_pages = serve(cfg, params, trace,
                                                 sharing, cap)
                dt = time.perf_counter() - t0
                if best is None or dt < best[0]:
                    best = (dt, eng, streams, peak_pages)
            dt, eng, streams, peak_pages = best
            toks = sum(len(s) for s in streams.values())
            row = {"policy": name, "tokens": toks, "tok_s": toks / dt,
                   "prefill_tokens": eng.stats["prefill_tokens"],
                   "shared_tokens": eng.stats["shared_tokens"],
                   "resume_shared_tokens":
                       eng.stats["resume_shared_tokens"],
                   "prefix_hits": eng.stats["prefix_hits"],
                   "cow_copies": eng.stats["cow_copies"],
                   "preemptions": eng.stats["preemptions"],
                   "peak_pages_held": peak_pages, "wall_s": dt}
            row.update(_latency_cols(eng))
            rows.append(row)
            streams_by[name] = streams
            print(f"# {scen}/{name}: {toks} tokens ({toks / dt:,.1f} "
                  f"tok/s), prefilled {eng.stats['prefill_tokens']} "
                  f"(shared {eng.stats['shared_tokens']}), peak pages "
                  f"{peak_pages}, {eng.stats['preemptions']} preempts, "
                  f"{eng.stats['cow_copies']} CoW copies",
                  file=sys.stderr)
            _emit(f"prefix_sharing_{scen}_{name}", dt * 1e6,
                  f"tok_s={toks / dt:.1f};"
                  f"prefill_toks={eng.stats['prefill_tokens']};"
                  f"peak_pages={peak_pages}")
        base, shared = rows
        sc = {"rows": rows,
              "streams_match": streams_by["unshared"] ==
              streams_by["shared"],
              "sharing_speedup": shared["tok_s"] / base["tok_s"],
              "prefill_tokens_ratio": base["prefill_tokens"] /
              shared["prefill_tokens"],
              "peak_pages_ratio": base["peak_pages_held"] /
              shared["peak_pages_held"]}
        out["scenarios"][scen] = sc
        print(f"# {scen}: streams_match={sc['streams_match']} "
              f"sharing_speedup={sc['sharing_speedup']:.2f}x "
              f"prefill-token ratio {sc['prefill_tokens_ratio']:.2f}x, "
              f"peak-pages ratio {sc['peak_pages_ratio']:.2f}x",
              file=sys.stderr)
        assert sc["streams_match"], \
            f"prefix sharing changed the streams ({scen})!"
    contended = out["scenarios"]["contended"]
    fanout = out["scenarios"]["fanout"]
    # legacy top-level keys = the contended scenario (the E10 headline)
    out["rows"] = contended["rows"]
    out["streams_match"] = (contended["streams_match"] and
                            fanout["streams_match"])
    out["sharing_speedup"] = contended["sharing_speedup"]
    out["prefill_tokens_ratio"] = contended["prefill_tokens_ratio"]
    out["peak_pages_ratio"] = contended["peak_pages_ratio"]
    # the acceptance gates: sharing wins (or at worst ties) under
    # contention, and fan-out shares past the seed prompt — decode-made
    # pages matched by later prompts, peak residency strictly down
    assert out["sharing_speedup"] >= 1.0, \
        f"sharing lost throughput: {out['sharing_speedup']:.2f}x"
    fan_shared = fanout["rows"][1]
    assert fan_shared["shared_tokens"] > \
        (len(fan_reqs) - 1) * len(fan_sys), \
        "fan-out never shared past the seed prompt"
    assert fanout["peak_pages_ratio"] > 1.0, \
        "fan-out sharing failed to reduce resident pages"
    _merge_snapshot(ROOT / "BENCH_serve.json", {"prefix_sharing": out})
    _history_append("prefix_sharing", {
        "scenarios": out["scenarios"],
        "streams_match": out["streams_match"],
        "sharing_speedup": out["sharing_speedup"],
        "prefill_tokens_ratio": out["prefill_tokens_ratio"],
        "peak_pages_ratio": out["peak_pages_ratio"]})


# ----------------------------------------------------------------- E11 -----

def bench_fault_overhead():
    """Price of the always-on fault guards (the cf4ocl "negligible
    overhead" claim, reproduced for serving).

    The same fault-free Poisson trace is served by the paged engine with
    ``guards=True`` (per-tick NaN/Inf scan over the sampled logits +
    deadline/cancellation sweep — the production default) and
    ``guards=False`` (the scan and sweep skipped).  No faults are
    injected, so the runs are byte-identical; the measured gap is pure
    guard cost.  Best-of-reps decode throughput; the acceptance target
    is < 2 % overhead (recorded as ``guards_lt_2pct``), with a lenient
    10 % hard bound so a noisy CI host cannot flake the lane.  Results
    land under the ``fault_overhead`` key of BENCH_serve.json.
    """
    import jax
    import numpy as np
    from repro.models.model import ModelConfig, init_params
    from repro.serve.engine import Request, ServeEngine

    cfg = ModelConfig(name="bench-serve", family="dense", num_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab=256, dtype="float32")
    n_slots, budget, reps = 4, 48, 3
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    rng = np.random.default_rng(42)
    arrivals = np.cumsum(rng.poisson(1.5, size=16))
    reqs = [Request(i, [int(t) for t in rng.integers(0, cfg.vocab,
                                                     rng.integers(4, 13))],
                    int(rng.integers(4, 17)), arrival=int(a))
            for i, a in enumerate(arrivals)]

    def serve(guards):
        eng = ServeEngine(cfg, params, n_slots=n_slots, budget=budget,
                          paged=True, page_size=4, guards=guards)
        streams = eng.run(reqs)
        return streams, eng.stats["decoded_tokens"], eng

    out = {"backend": jax.default_backend(),
           "trace": {"n_requests": len(reqs), "n_slots": n_slots,
                     "budget": budget, "reps": reps},
           "rows": []}
    streams_by, tok_s_by = {}, {}
    for name, guards in [("guards_off", False), ("guards_on", True)]:
        serve(guards)                           # warmup (jit compile)
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            streams, decoded, eng = serve(guards)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        streams_by[name] = streams
        tok_s_by[name] = decoded / best
        out["rows"].append({"policy": name, "decoded_tokens": decoded,
                            "tok_s": tok_s_by[name], "wall_s": best,
                            **_latency_cols(eng)})
        print(f"# {name}: {decoded} decode tokens in {best:.3f}s "
              f"({tok_s_by[name]:,.1f} tok/s)", file=sys.stderr)
        _emit(f"fault_overhead_{name}", best * 1e6,
              f"tok_s={tok_s_by[name]:.1f}")
    out["streams_match"] = streams_by["guards_off"] == \
        streams_by["guards_on"]
    out["overhead_frac"] = max(
        0.0, 1.0 - tok_s_by["guards_on"] / tok_s_by["guards_off"])
    out["guards_lt_2pct"] = out["overhead_frac"] < 0.02
    print(f"# streams_match={out['streams_match']} guard overhead "
          f"{out['overhead_frac'] * 100:.2f}% "
          f"(<2%: {out['guards_lt_2pct']})", file=sys.stderr)
    assert out["streams_match"], "guards changed fault-free streams!"
    assert out["overhead_frac"] < 0.10, \
        f"guard path costs {out['overhead_frac'] * 100:.1f}% decode tok/s"
    _merge_snapshot(ROOT / "BENCH_serve.json", {"fault_overhead": out})
    _history_append("fault_overhead", {
        "rows": out["rows"], "streams_match": out["streams_match"],
        "overhead_frac": out["overhead_frac"],
        "guards_lt_2pct": out["guards_lt_2pct"]})


# ----------------------------------------------------------------- E12 -----

def bench_elastic_batching():
    """Shape-bucketed serving vs exact-shape serving (the jit retrace
    storm), same Poisson trace with 13 distinct prompt lengths.

    ``bucketed`` draws every step shape from the static ladders (packed
    decode widths, prompt length buckets) — compile count is bounded by
    the ladder sizes; ``fixed`` (``buckets=False``) retraces prefill
    once per distinct prompt length and always decodes at full width.
    Each mode gets one *cold* run under a fresh config name (compile-
    inclusive wall time + compile counts from ``stats["compiles"]``),
    then best-of-reps warm runs for steady-state decode tok/s.  Prompt
    lengths stay in the bit-exact padding regime, so the two modes must
    stream byte-identically; acceptance: bucketed compiles at most one
    prefill per ladder rung and steady-state tok/s is no worse than
    fixed (lenient 0.8× hard bound for noisy CI hosts).  Results land
    under the ``elastic_batching`` key of BENCH_serve.json.
    """
    import dataclasses

    import jax
    import numpy as np
    from repro.models.model import ModelConfig, init_params
    from repro.serve.engine import Request, ServeEngine

    base = ModelConfig(name="bench-elastic", family="dense", num_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                       d_ff=128, vocab=256, dtype="float32")
    n_slots, budget, reps = 4, 48, 3

    rng = np.random.default_rng(42)
    lengths = list(range(4, 17)) + [6, 10, 14]      # 13 distinct of 16
    rng.shuffle(lengths)
    arrivals = np.cumsum(rng.poisson(1.5, size=len(lengths)))
    prompts = [[int(t) for t in rng.integers(0, base.vocab, L)]
               for L in lengths]
    news = [int(rng.integers(4, 17)) for _ in lengths]

    def serve(cfg, buckets):
        reqs = [Request(i, p, n, arrival=int(a))
                for i, (p, n, a) in enumerate(zip(prompts, news, arrivals))]
        eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                          n_slots=n_slots, budget=budget, buckets=buckets)
        streams = eng.run(reqs)
        return streams, eng.stats["decoded_tokens"], \
            dict(eng.stats["compiles"]), eng

    out = {"backend": jax.default_backend(),
           "trace": {"n_requests": len(lengths), "n_slots": n_slots,
                     "budget": budget, "reps": reps,
                     "distinct_prompt_lengths": len(set(lengths))},
           "rows": []}
    streams_by, tok_s_by, compiles_by = {}, {}, {}
    for name, buckets in [("bucketed", True), ("fixed", False)]:
        # fresh config name → cold process-global jit caches: the cold
        # run prices the compile storm (or its absence)
        cfg = dataclasses.replace(base, name=f"bench-elastic-{name}")
        t0 = time.perf_counter()
        streams, decoded, compiles, eng = serve(cfg, buckets)
        cold = time.perf_counter() - t0
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            streams, decoded, _, eng = serve(cfg, buckets)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        streams_by[name] = streams
        tok_s_by[name] = decoded / best
        compiles_by[name] = compiles
        out["rows"].append({"mode": name, "compiles": compiles,
                            "total_compiles": sum(compiles.values()),
                            "decoded_tokens": decoded,
                            "cold_wall_s": cold, "wall_s": best,
                            "tok_s": tok_s_by[name],
                            **_latency_cols(eng)})
        print(f"# {name}: compiles={compiles} cold={cold:.3f}s "
              f"warm {decoded} tokens in {best:.3f}s "
              f"({tok_s_by[name]:,.1f} tok/s)", file=sys.stderr)
        _emit(f"elastic_batching_{name}", best * 1e6,
              f"tok_s={tok_s_by[name]:.1f} "
              f"compiles={sum(compiles.values())}")
    out["streams_match"] = streams_by["bucketed"] == streams_by["fixed"]
    out["compile_ratio"] = sum(compiles_by["fixed"].values()) / max(
        1, sum(compiles_by["bucketed"].values()))
    out["tok_s_ratio"] = tok_s_by["bucketed"] / tok_s_by["fixed"]
    print(f"# streams_match={out['streams_match']} compile ratio "
          f"{out['compile_ratio']:.1f}x  tok/s ratio "
          f"{out['tok_s_ratio']:.2f}x", file=sys.stderr)
    assert out["streams_match"], "bucketing changed exact-regime streams!"
    assert compiles_by["bucketed"]["prefill"] < \
        out["trace"]["distinct_prompt_lengths"], \
        "bucketed prefill compiled once per length — no bucketing?"
    assert out["tok_s_ratio"] > 0.8, \
        f"bucketed serving lost {(1 - out['tok_s_ratio']) * 100:.0f}% tok/s"
    _merge_snapshot(ROOT / "BENCH_serve.json", {"elastic_batching": out})
    _history_append("elastic_batching", {
        "rows": out["rows"], "streams_match": out["streams_match"],
        "compile_ratio": out["compile_ratio"],
        "tok_s_ratio": out["tok_s_ratio"]})


BENCHES = {
    "loc_compare": bench_loc_compare,
    "overhead": bench_overhead,
    "prof_summary": bench_prof_summary,
    "queue_chart": bench_queue_chart,
    "prng_quality": bench_prng_quality,
    "roofline": bench_roofline,
    "decode_throughput": bench_decode_throughput,
    "serve_throughput": bench_serve_throughput,
    "paged_vs_dense": bench_paged_vs_dense,
    "prefix_sharing": bench_prefix_sharing,
    "fault_overhead": bench_fault_overhead,
    "elastic_batching": bench_elastic_batching,
}


def main() -> None:
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main()
