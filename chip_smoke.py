"""Chip smoke: serve full-width SmolLM-360M on one TPU through ServeEngine.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py

Every phase runs in this one process (a chip belongs to one process) and
the first failure stops the run with a non-zero exit:

(a) the device: JAX must have come up on a TPU.  When the TPU fails to
    initialise JAX falls back to the CPU, where the Pallas kernels would
    quietly run in the interpreter; this check refuses that.
(b) serving: ``ServeEngine`` over the paged KV pool with the Pallas flash
    (prefill) and fused decode kernels, 8 slots, a 2048-position budget,
    128-position pages, seeded random weights.  16 seeded requests
    (prompts of 128–1024 tokens, 32 new tokens each, Poisson arrivals)
    must all finish with their full token count; the engine's NaN/Inf
    guard fails any request whose logits are not finite, so "none
    FAILED" also means every logit row was finite.  The same requests
    served again through a fresh engine must give the same streams.
(c) kernels against their references: at the engine's exact shapes,
    ``decode_attention`` (dense ring and paged pool) and
    ``flash_attention`` (position planes, every prefill length bucket)
    with ``impl="pallas"`` against ``impl="xla"``: the attention output
    within two bf16 ulps of the reference (``REL_TOL``), and the cache
    writes identical.

The numbers printed along the way are this smoke's own, measured once and
cold: they show that the path runs, and are not a benchmark.  The last
line of standard output is a JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Both sides accumulate in f32 and round the output to bf16, whose ulp
# at |x| is between 2**-8·|x| and 2**-7·|x|: allow two ulps,
# |pallas - xla| <= REL_TOL · max(|xla|, 1), elementwise
REL_TOL = 2.0 ** -6


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_requests(cfg, *, n_requests: int, prompt_lens, new_tokens: int,
                  mean_gap: float, seed: int):
    """Seeded traffic: uniform prompt lengths in ``prompt_lens``
    (inclusive), Poisson inter-arrival gaps in engine ticks."""
    import numpy as np
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.poisson(mean_gap, size=n_requests))
    lo, hi = prompt_lens
    return [Request(i, rng.integers(0, cfg.vocab, rng.integers(lo, hi + 1)
                                    ).tolist(),
                    new_tokens, arrival=int(a))
            for i, a in enumerate(arrivals)]


def _serve(cfg, params, reqs, *, n_slots, budget, page_size):
    """Serve ``reqs`` through a fresh engine and check every request
    finished whole; the engine (and its KV pool) is dropped on return."""
    from repro.serve.engine import ServeEngine, Status

    eng = ServeEngine(cfg, params, n_slots=n_slots, budget=budget,
                      paged=True, page_size=page_size,
                      max_submission_retries=0)
    t0 = time.perf_counter()
    streams = eng.run(reqs)
    wall = time.perf_counter() - t0
    for seq in eng.sequences:
        _check(seq.status is Status.FINISHED,
               f"request {seq.rid} ended {seq.status.value}: {seq.error}")
        _check(len(streams[seq.rid]) == seq.request.max_new_tokens,
               f"request {seq.rid}: {len(streams[seq.rid])} tokens, "
               f"expected {seq.request.max_new_tokens}")
    return {"streams": streams, "wall_s": wall,
            "compile_s": sum(ev.duration_ns
                             for ev in eng.compile_events) / 1e9,
            "compiles": dict(eng.stats["compiles"]),
            "lengths": eng._registry.lengths}


def _decode_inputs(cfg, key, B, S):
    import jax
    import jax.numpy as jnp

    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (B, Hq, 1, D), dt),
            jax.random.normal(ks[1], (B, Hkv, S, D), dt),
            jax.random.normal(ks[2], (B, Hkv, S, D), dt),
            jax.random.normal(ks[3], (B, Hkv, 1, D), dt),
            jax.random.normal(ks[4], (B, Hkv, 1, D), dt))


def _errors(got, want):
    """``(max |got - want|, max of |got - want| over its allowance)``;
    the check holds when the second is at most 1."""
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    err = np.abs(g - w)
    allowed = REL_TOL * np.maximum(np.abs(w), 1.0)
    return float(err.max()), float((err / allowed).max())


def compare_kernels(cfg, *, n_slots: int, budget: int, page_size: int,
                    lengths, seed: int) -> dict:
    """Phase (c): Pallas against the XLA reference at the engine's
    shapes; returns ``{case: (max |error|, worst error over its
    allowance)}`` for the attention output."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    errs = {}

    def check_out(name, got, want):
        errs[name] = _errors(got, want)
        _check(errs[name][1] <= 1.0,
               f"{name}: |pallas - xla| up to {errs[name][1]} times the "
               f"allowed {REL_TOL}·max(|xla|, 1)")

    def run_both(name, fn):
        got, want = fn("pallas"), fn("xla")
        check_out(name, got[0], want[0])
        for g, w, leaf in zip(got[1:], want[1:], ("k", "v", "pos")):
            _check(np.array_equal(np.asarray(g), np.asarray(w)),
                   f"{name}: {leaf} cache writes differ")

    # dense ring at the decode budget: every slot at its own depth
    B, S = n_slots, budget
    q, kc, vc, kn, vn = _decode_inputs(cfg, key, B, S)
    fills = rng.integers(1, S, size=B)
    j = np.arange(S)
    pc = jnp.asarray(np.where(j[None] < fills[:, None], j[None], -1),
                     jnp.int32)
    pos = jnp.asarray(fills, jnp.int32)
    run_both("decode_dense", lambda impl: decode_attention(
        q, kc, vc, pc, kn, vn, pos, impl=impl))

    # paged pool: the engine's arena (full provision + null page 0),
    # each row's logical pages scattered over the arena
    n_ptes = budget // page_size
    n_pages = n_slots * n_ptes + 1
    q, ka, va, kn, vn = _decode_inputs(cfg, jax.random.fold_in(key, 1),
                                       n_pages, page_size)
    q, kn, vn = q[:n_slots], kn[:n_slots], vn[:n_slots]
    table = (1 + rng.permutation(n_pages - 1)).reshape(n_slots, n_ptes)
    parena = np.full((n_pages, page_size), -1, np.int32)
    for b in range(n_slots):
        for t in range(n_ptes):
            p = t * page_size + np.arange(page_size)
            parena[table[b, t]] = np.where(p < fills[b], p, -1)
    run_both("decode_paged", lambda impl: decode_attention(
        q, ka, va, jnp.asarray(parena), kn, vn, pos, impl=impl,
        page_table=jnp.asarray(table, jnp.int32)))

    # flash prefill with position planes at every length bucket; the
    # tail past the prompt is padding (pos = -1), as admission pads it
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    for T in lengths:
        ks = jax.random.split(jax.random.fold_in(key, T), 3)
        qf = jax.random.normal(ks[0], (1, Hq, T, D), dt)
        kf = jax.random.normal(ks[1], (1, Hkv, T, D), dt)
        vf = jax.random.normal(ks[2], (1, Hkv, T, D), dt)
        true_len = int(rng.integers(T // 2 + 1, T + 1))
        ar = np.arange(T)
        pp = jnp.asarray(np.where(ar < true_len, ar, -1)[None], jnp.int32)
        outs = {impl: flash_attention(qf, kf, vf, causal=True, impl=impl,
                                      q_pos=pp, k_pos=pp)
                for impl in ("pallas", "xla")}
        check_out(f"flash_T{T}", outs["pallas"][:, :, :true_len],
                  outs["xla"][:, :, :true_len])
    return errs


def serve_and_compare(cfg, *, n_slots: int = 8, budget: int = 2048,
                      page_size: int = 128, n_requests: int = 16,
                      prompt_lens=(128, 1024), new_tokens: int = 32,
                      mean_gap: float = 2.0, seed: int = 0) -> dict:
    """Phases (b) and (c) for ``cfg`` (its ``attn_impl`` is forced to
    ``"pallas"``); raises :class:`SmokeFailure` on the first check that
    does not hold and returns the smoke's numbers otherwise."""
    import jax
    from repro.models.model import init_params

    cfg = dataclasses.replace(cfg, attn_impl="pallas")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    reqs = make_requests(cfg, n_requests=n_requests, prompt_lens=prompt_lens,
                         new_tokens=new_tokens, mean_gap=mean_gap, seed=seed)

    kw = dict(n_slots=n_slots, budget=budget, page_size=page_size)
    first = _serve(cfg, params, reqs, **kw)
    again = _serve(cfg, params, reqs, **kw)
    _check(again["streams"] == first["streams"],
           "a second serve of the same requests gave different streams")
    tokens = sum(len(s) for s in first["streams"].values())

    errs = compare_kernels(cfg, lengths=first["lengths"], seed=seed, **kw)
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "requests": len(reqs),
        "tokens": tokens,
        "compiles": first["compiles"],
        "compile_s": first["compile_s"],
        "first_wall_s": first["wall_s"],
        "again_wall_s": again["wall_s"],
        "again_tok_s": tokens / again["wall_s"],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "kernel_errors": errs,
    }


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX came up on {dev.platform!r} "
              f"({dev.device_kind}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.core.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    try:
        res = serve_and_compare(get_config("smollm-360m"))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print("smoke numbers (one run each; a smoke, not a benchmark):")
    print(f"  requests finished        {res['requests']}/{res['requests']},"
          f" {res['tokens']} tokens, none FAILED")
    print(f"  jit compiles             {res['compiles']}")
    print(f"  compile seconds          {res['compile_s']} (first serve; "
          f"persistent cache {cache_dir})")
    print(f"  serve wall s             {res['first_wall_s']} first, "
          f"{res['again_wall_s']} again (compiled)")
    print(f"  wall tokens/s            {res['again_tok_s']} (again)")
    print(f"  peak_bytes_in_use        {res['peak_bytes_in_use']}")
    print(f"kernels, pallas vs xla: max |error|, and worst error over "
          f"its allowance {REL_TOL}·max(|xla|, 1) (must be <= 1):")
    for name, (err, ratio) in res["kernel_errors"].items():
        print(f"  {name:24s} {err}  {ratio}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
