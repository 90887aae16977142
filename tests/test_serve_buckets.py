"""Shape-bucketed serving: every jitted step runs at a shape drawn from
a small static ladder, compiled once per rung.

The conformance contract: (1) in the bit-exact regime a bucketed prefill
plus dynamic alignment reproduces the exact-shape path to the last bit;
(2) the bucketed engine — packed decode widths, length-padded prefills,
batched copy-on-write — streams byte-identically to a bucket-aware
fixed-width lockstep oracle on both the xla and pallas-interpret decode
paths; (3) gather/scatter row packing round-trips any active-slot set
(property test); (4) the retrace gate — a trace with eight-plus distinct
prompt lengths compiles at most one prefill per length rung and one
decode per width rung, observable through ``stats["compiles"]`` and the
``TRACE_COMPILE`` profiler events."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import model as M
from repro.models.attention import KVCache
from repro.models.model import ModelConfig
from repro.serve import paging as P
from repro.serve.engine import PagedCacheManager, Request, ServeEngine
from repro.serve.step import (BucketRegistry, align_prefill_cache,
                              align_prefill_cache_dyn, length_ladder,
                              make_decode_step, make_prefill_step,
                              width_ladder)

KEY = jax.random.PRNGKey(7)


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="tiny-buckets", family="dense", num_layers=2,
                d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                vocab=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


DENSE = tiny_cfg()
SWA = tiny_cfg(pattern=(("swa", "dense"),), window=6)
CHUNKED = tiny_cfg(pattern=(("chunked", "dense"),), chunk=8)
# swa ring wraps into shared pages during decode → copy-on-write
HYBRID = tiny_cfg(name="tiny-buckets-hybrid",
                  pattern=(("swa", "dense"), ("full", "dense")), window=16)
REC = tiny_cfg(name="tiny-buckets-rec", family="hybrid",
               pattern=(("rec", "dense"), ("full", "dense")),
               lru_width=32, conv_kernel=4)


def mk_trace(vocab, spec):
    rng = np.random.default_rng(17)
    return [Request(i, [int(t) for t in rng.integers(0, vocab, L)],
                    n, arrival=a)
            for i, (L, n, a) in enumerate(spec)]


def lockstep_bucket(cfg, params, prompt, max_new, budget,
                    prefill_impl="xla", page_size=None):
    """Fixed-width lockstep oracle under the engine's length bucketing:
    one request at a time, batch width 1 throughout — bucketed prefill
    (the same jitted program the engine runs, so padded-reduction
    numerics agree by construction) → dynamic align → the classic
    exact-shape decode loop, greedy.  Decode-width packing is the one
    thing the engine does that this path does not, which is exactly what
    stream equality then proves."""
    pcfg = dataclasses.replace(cfg, attn_impl=prefill_impl)
    reg = BucketRegistry(cfg, n_slots=1, budget=budget,
                         page_size=page_size, prefill_cfg=pcfg)
    decode = make_decode_step(cfg)
    L = len(prompt)
    Lb = reg.len_bucket(L)
    toks = np.zeros((1, Lb), np.int32)
    toks[0, :L] = prompt
    logits, cache, _ = reg.prefill(Lb)(params, jnp.asarray(toks),
                                       jnp.int32(L))
    cache = align_prefill_cache_dyn(cfg, cache, L, budget)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = L
    while len(out) < max_new:
        logits, cache, _ = decode(params, cache,
                                  jnp.asarray([[out[-1]]], jnp.int32),
                                  jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


# ---------------------------------------------- ladders (pure functions) ----

def test_ladders():
    assert width_ladder(1) == (1,)
    assert width_ladder(4) == (1, 2, 4)
    assert width_ladder(6) == (1, 2, 4, 6)
    assert length_ladder(8, 48) == (8, 16, 32, 64)
    assert length_ladder(4, 16) == (4, 8, 16)
    reg = BucketRegistry(DENSE, n_slots=3, budget=24)
    assert reg.widths == (1, 2, 3)
    assert [reg.width_bucket(n) for n in (0, 1, 2, 3)] == [1, 1, 2, 3]
    assert reg.len_bucket(5) == 8 and reg.len_bucket(17) == 32
    off = BucketRegistry(DENSE, n_slots=3, budget=24, bucketing=False)
    assert off.widths == (3,) and off.len_bucket(5) == 5
    # recurrent state caches: length bucketing off, width packing on
    rec = BucketRegistry(REC, n_slots=4, budget=24)
    assert rec.lengths == () and rec.len_bucket(5) == 5
    assert rec.widths == (1, 2, 4)


# ------------------------------- bucketed prefill ≡ exact (bit-exact) -------

@pytest.mark.parametrize("cfg", [DENSE, SWA, CHUNKED],
                         ids=["full", "swa-ring", "chunked"])
def test_bucket_prefill_align_matches_exact(cfg):
    """For prompts whose padded span stays in the bit-exact regime, the
    bucketed prefill + dynamic align must reproduce the exact-shape
    prefill + static align to the last bit: final-position logits and
    every ring leaf (K, V, positions) of the aligned cache."""
    budget = 16
    params = M.init_params(cfg, KEY)
    prefill = make_prefill_step(cfg)
    reg = BucketRegistry(cfg, n_slots=1, budget=budget)
    rng = np.random.default_rng(3)
    for L in (3, 5, 8, 11, 13, 16):
        prompt = rng.integers(0, cfg.vocab, (1, L)).astype(np.int32)
        lg_e, c_e = prefill(params, jnp.asarray(prompt))
        ring_e = align_prefill_cache(cfg, c_e, L, target_len=budget)

        Lb = reg.len_bucket(L)
        padded = np.zeros((1, Lb), np.int32)
        padded[:, :L] = prompt
        lg_b, c_b, _ = reg.prefill(Lb)(params, jnp.asarray(padded),
                                       jnp.int32(L))
        ring_b = align_prefill_cache_dyn(cfg, c_b, L, budget)

        assert np.array_equal(np.asarray(lg_e[0, -1]),
                              np.asarray(lg_b[0, -1])), f"logits @ L={L}"
        for le, lb in zip(jax.tree.leaves(ring_e), jax.tree.leaves(ring_b)):
            assert np.array_equal(np.asarray(le), np.asarray(lb)), \
                f"ring leaf mismatch @ L={L}"


# --------------------------- engine ≡ bucket-aware lockstep (end-to-end) ----

# eight requests, six distinct prompt lengths spanning both sides of the
# bit-exact padding boundary, staggered so the active set sweeps widths
# 1→3 (packed decode at every ladder rung)
LTRACE = [(17, 4, 0), (20, 5, 0), (23, 3, 1), (26, 4, 2),
          (30, 3, 4), (17, 5, 6), (12, 4, 7), (9, 3, 8)]


@pytest.mark.parametrize("cfg", [DENSE, SWA, CHUNKED],
                         ids=["full", "swa-ring", "chunked"])
def test_engine_buckets_match_oracle_xla(cfg):
    """Long prompts (padding changes reduction shapes) under staggered
    arrivals: the bucketed engine must stream byte-identically to the
    per-request fixed-width oracle."""
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, LTRACE)
    eng = ServeEngine(cfg, params, n_slots=3, budget=40)
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_bucket(cfg, params, r.prompt, r.max_new_tokens, 40)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    # the packed widths were actually exercised and nothing over-compiled
    assert 1 <= eng.stats["compiles"]["decode"] <= len(eng._registry.widths)
    assert eng.tick < sum(n for _, n, _ in LTRACE)


def test_engine_buckets_match_oracle_pallas():
    """Same contract on the fused Pallas decode (interpret mode on CPU)
    with xla prefill — packed (W,) ring writes inside the kernel."""
    cfg = dataclasses.replace(SWA, attn_impl="pallas")
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, [(17, 3, 0), (21, 4, 1), (26, 3, 3),
                                (12, 3, 5)])
    eng = ServeEngine(cfg, params, n_slots=2, budget=32,
                      prefill_impl="xla")
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_bucket(cfg, params, r.prompt, r.max_new_tokens, 32)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"


def test_engine_buckets_paged_sharing_cow():
    """Paged pool + prefix sharing under buckets: two sequences share a
    2-page prefix through the bucketed partial prefill (padded prefix
    gather), decode wraps the swa ring into the shared pages (batched
    copy-on-write on the Decode lane), and a long unshared latecomer
    exercises the padded one-shot path — all streams byte-identical to
    the oracle."""
    cfg = HYBRID
    params = M.init_params(cfg, KEY)
    pre = [int(t) for t in np.random.default_rng(3).integers(0, 128, 8)]
    reqs = [Request(0, pre + [5, 9], 13, arrival=0),
            Request(1, pre + [7, 3], 13, arrival=0),
            Request(2, [int(t) for t in
                        np.random.default_rng(9).integers(0, 128, 18)],
                    6, arrival=2)]
    eng = ServeEngine(cfg, params, n_slots=3, budget=24, paged=True,
                      page_size=4, prefill_impl="xla")
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_bucket(cfg, params, r.prompt, r.max_new_tokens, 24,
                              page_size=4)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["cow_copies"] >= 1, \
        "the trace was meant to wrap into a shared page"
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


# -------------------------------------------------------- retrace gate ------

def test_retrace_gate_multilength_trace():
    """CI gate for the tentpole claim: a Poisson-staggered trace with
    eight-plus distinct prompt lengths compiles at most one prefill per
    length rung and one decode per width rung (fresh config name → cold
    process-global jit caches, so the counts are real compiles)."""
    cfg = tiny_cfg(name="tiny-bucket-gate")
    params = M.init_params(cfg, KEY)
    rng = np.random.default_rng(29)
    lengths = [3, 5, 7, 9, 12, 14, 17, 20, 23, 26, 30, 11]
    arrivals = np.cumsum(rng.poisson(1.2, len(lengths)))
    reqs = [Request(i, [int(t) for t in rng.integers(0, cfg.vocab, L)],
                    int(rng.integers(2, 5)), arrival=int(a))
            for i, (L, a) in enumerate(zip(lengths, arrivals))]
    assert len(set(lengths)) >= 8
    eng = ServeEngine(cfg, params, n_slots=4, budget=48)
    eng.run(reqs)
    reg = eng._registry
    c = eng.stats["compiles"]
    assert 1 <= c["prefill"] <= len(reg.lengths), c
    assert 1 <= c["decode"] <= len(reg.widths), c
    assert c.get("align", 0) <= len(reg.lengths), c
    # observability: live counter dict + timed TRACE_COMPILE events
    assert c is reg.compiles
    assert len(eng.compile_events) == sum(c.values())
    for ev in eng.compile_events:
        assert ev.name.startswith("TRACE_COMPILE:")
        assert ev.duration_ns is not None and ev.duration_ns > 0


def test_warmup_precompiles_ladders():
    """Eager warmup takes every ladder compile up front; serving traffic
    afterwards must not trace anything new."""
    cfg = tiny_cfg(name="tiny-bucket-warm")
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=3, budget=24)
    eng.warmup()
    c0 = dict(eng.stats["compiles"])
    assert c0["prefill"] == len(eng._registry.lengths)
    assert c0["decode"] == len(eng._registry.widths)
    assert c0["align"] == len(eng._registry.lengths)
    streams = eng.run(mk_trace(cfg.vocab, [(5, 4, 0), (9, 7, 0), (3, 2, 1),
                                           (7, 5, 3), (4, 6, 4)]))
    assert len(streams) == 5 and all(streams.values())
    assert eng.stats["compiles"] == c0, "warm ladders must not retrace"


# --------------------------------------- pack/unpack row movement (prop) ----

def _numbered_cache(cfg, n_slots, budget):
    """A decode cache whose slot rows all hold distinct values, so any
    misrouted row shows up as a concrete mismatch.  Values stay below a
    prime modulus small enough that value and value+1 are exact in every
    cache dtype (bf16 state leaves round above 256); slot strides are
    powers of two, so rows of different slots can never alias mod 113."""
    counter = [0]

    def fill(a):
        base = counter[0]
        counter[0] += a.size
        vals = ((base + np.arange(a.size)) % 113).reshape(a.shape)
        return jnp.asarray(vals.astype(np.asarray(a).dtype))

    return jax.tree.map(fill, M.cache_init(cfg, n_slots, budget))


@given(st.integers(2, 5), st.lists(st.booleans(), min_size=5, max_size=5))
@settings(max_examples=12, deadline=None)
def test_pack_unpack_roundtrip(n_slots, mask):
    """gather→scatter over an arbitrary active-slot set is the identity
    on the standing cache (padding rows drop), and a mutation applied to
    the packed rows lands in exactly the active slots — on KV rings and
    recurrent state leaves alike."""
    cfg = REC
    cache = _numbered_cache(cfg, n_slots, 16)
    active = [s for s in range(n_slots) if mask[s]]
    W = 1
    while W < max(1, len(active)):
        W *= 2
    rows = np.full((W,), n_slots, np.int32)     # n_slots == padding
    rows[:len(active)] = active

    packed = P.gather_batch_rows(cfg, cache, rows)
    for le, lp in zip(jax.tree.leaves(cache), jax.tree.leaves(packed)):
        le, lp = np.asarray(le), np.asarray(lp)
        for i, s in enumerate(rows):
            if s < n_slots:
                assert np.array_equal(lp[:, i], le[:, s])

    back = P.scatter_batch_rows(cfg, cache, packed, rows)
    for le, lb in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(le), np.asarray(lb))

    bumped = jax.tree.map(lambda a: a + 1, packed)
    out = P.scatter_batch_rows(cfg, cache, bumped, rows)
    for le, lo in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
        le, lo = np.asarray(le), np.asarray(lo)
        for s in range(n_slots):
            if s in active:
                assert np.array_equal(lo[:, s], le[:, s] + 1)
            else:
                assert np.array_equal(lo[:, s], le[:, s])


def test_pack_unpack_paged_pass_through():
    """Paged caches move only the slot-indexed leaves: gather selects
    page-table rows (padding rows all-null) and shares the arenas by
    identity; scatter adopts the packed arenas and keeps the standing
    full-width table."""
    cfg = tiny_cfg(name="tiny-bucket-paged")
    mgr = PagedCacheManager(cfg, 4, 16, page_size=4)
    counter = [1]

    def fill_tbl(c):
        if not isinstance(c, KVCache) or c.page_table is None:
            return c
        n = c.page_table.size
        t = ((counter[0] + np.arange(n)) % 7 + 1).reshape(c.page_table.shape)
        counter[0] += n
        return KVCache(c.k, c.v, c.pos, jnp.asarray(t.astype(np.int32)))

    cache = jax.tree.map(fill_tbl, mgr.cache,
                         is_leaf=lambda x: isinstance(x, KVCache))
    rows = np.asarray([2, 0, 4, 4], np.int32)   # two active, two padding
    packed = P.gather_batch_rows(cfg, cache, rows)
    back = P.scatter_batch_rows(cfg, cache, packed, rows)
    for gi, (kinds, _) in enumerate(M.cache_layout(cfg)):
        for pi, kind in enumerate(kinds):
            c = cache["groups"][gi][pi]
            p = packed["groups"][gi][pi]
            b = back["groups"][gi][pi]
            if not (isinstance(c, KVCache) and c.page_table is not None):
                continue
            assert p.k is c.k and p.v is c.v     # arenas pass through
            pt = np.asarray(p.page_table)
            ct = np.asarray(c.page_table)
            assert np.array_equal(pt[:, 0], ct[:, 2])
            assert np.array_equal(pt[:, 1], ct[:, 0])
            assert (pt[:, 2:] == P.PAGE_NULL).all()
            assert b.k is p.k                    # arenas adopted back
            assert b.page_table is c.page_table  # standing table kept
