"""Paged KV-cache pool: the allocator's free-list invariants
(property-style), the paged decode op against the dense oracle under
arbitrary page placements, and the end-to-end proof — a paged engine
(including one running preemption under an oversubscribed pool) must
produce the exact token streams of the dense lockstep reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import (decode_attention_paged_ref,
                                                decode_attention_ref)
from repro.models import model as M
from repro.models.model import ModelConfig
from repro.serve import paging as P
from repro.serve.engine import PagedCacheManager, Request, ServeEngine
from repro.serve.step import (align_prefill_cache, make_decode_step,
                              make_prefill_step)

KEY = jax.random.PRNGKey(11)


# ------------------------------------------------------- PageAllocator -----

@settings(max_examples=40)
@given(st.integers(2, 24),
       st.lists(st.integers(0, 6), min_size=1, max_size=30),
       st.integers(0, 2 ** 31))
def test_allocator_roundtrip(n_pages, sizes, seed):
    """Random alloc/free interleavings: grants are disjoint, never include
    the null page, exhaustion is all-or-nothing (and beyond-capacity asks
    raise), and every page freed returns to circulation (conservation)."""
    rng = np.random.default_rng(seed)
    alloc = P.PageAllocator(n_pages)
    capacity = n_pages - 1
    held = []
    for n in sizes:
        if n > capacity:                # could never be granted: caller bug
            with pytest.raises(ValueError):
                alloc.alloc(n)
        elif n > capacity - sum(len(h) for h in held):
            assert alloc.alloc(n) is None   # all-or-nothing on exhaustion
        else:
            got = alloc.alloc(n)
            assert got is not None and len(got) == n
            assert P.PAGE_NULL not in got
            flat = [p for h in held for p in h]
            assert not set(got) & set(flat), "page double-granted"
            held.append(got)
        if held and rng.integers(0, 2):
            alloc.free(held.pop(rng.integers(0, len(held))))
        assert alloc.n_free + alloc.n_held == capacity
    for h in held:
        alloc.free(h)
    assert alloc.n_free == capacity and alloc.n_held == 0
    # deterministic: lowest ids first after everything came back
    assert alloc.alloc(min(3, capacity)) == list(
        range(1, 1 + min(3, capacity)))


def test_allocator_double_free_is_error():
    alloc = P.PageAllocator(4)
    got = alloc.alloc(2)
    alloc.free(got)
    with pytest.raises(AssertionError):
        alloc.free([got[0]])
    with pytest.raises(AssertionError):
        alloc.free([99])                # foreign page


def test_allocator_negative_paths_leave_free_list_intact():
    """Freeing an unallocated page, asking beyond the arena capacity, and
    a stale table sync must raise without corrupting the free list."""
    alloc = P.PageAllocator(5)          # capacity 4
    before = alloc.n_free
    with pytest.raises(AssertionError):
        alloc.free([2])                 # never allocated
    with pytest.raises(ValueError):
        alloc.alloc(5)                  # beyond capacity: can never succeed
    assert alloc.n_free == before and alloc.n_held == 0
    got = alloc.alloc(4)                # the full arena still grants
    assert got == [1, 2, 3, 4]
    assert alloc.alloc(1) is None       # transient exhaustion stays None
    alloc.free(got)
    assert alloc.n_free == before


def test_table_sync_with_stale_entry_raises():
    """A page-table entry naming a page the allocator no longer holds
    must fail sync before it reaches the device (decode through it would
    read a freed page)."""
    mgr = PagedCacheManager(DENSE, 2, 16, page_size=4)
    assert mgr.admit_pages(0, 7)
    mgr.sync()                          # healthy tables sync fine
    (page,) = mgr.alloc["full"].alloc(1)
    mgr.alloc["full"].free([page])      # allocated then freed: stale
    mgr.tables["full"][1, 0] = page     # simulate a buggy row mutation
    mgr._touched["full"].add(1)         # (mutation helpers record these)
    mgr._dirty = True
    with pytest.raises(AssertionError, match="stale page-table entry"):
        mgr.sync()
    # undo the poke: the manager must still be usable
    mgr.tables["full"][1, 0] = P.PAGE_NULL
    mgr.sync()


# ------------------------------------------- paged op vs dense oracle ------

def ring_pos(B, S, pos):
    j = jnp.arange(S)
    if pos == 0:
        return jnp.full((B, S), -1, jnp.int32)
    newest = pos - 1
    p = newest - jnp.mod(newest - j, S)
    return jnp.broadcast_to(jnp.where(p >= 0, p, -1)[None], (B, S)
                            ).astype(jnp.int32)


def paged_view(kc, vc, pc, ps, perm_seed=0, extra_pages=2):
    """Scatter dense rings into an arena under a shuffled page table."""
    B, Hkv, W, D = kc.shape
    n_ptes = W // ps
    n_pages = 1 + B * n_ptes + extra_pages
    rng = np.random.default_rng(perm_seed)
    ids = 1 + rng.permutation(n_pages - 1)[:B * n_ptes]
    pt = jnp.asarray(ids.reshape(B, n_ptes), jnp.int32)
    ka = jnp.zeros((n_pages, Hkv, ps, D), kc.dtype)
    va = jnp.zeros_like(ka)
    pa = jnp.full((n_pages, ps), -1, jnp.int32)
    flat = pt.reshape(-1)
    ka = ka.at[flat].set(
        kc.reshape(B, Hkv, n_ptes, ps, D).transpose(0, 2, 1, 3, 4)
        .reshape(-1, Hkv, ps, D))
    va = va.at[flat].set(
        vc.reshape(B, Hkv, n_ptes, ps, D).transpose(0, 2, 1, 3, 4)
        .reshape(-1, Hkv, ps, D))
    pa = pa.at[flat].set(pc.reshape(-1, ps))
    return ka, va, pa, pt


SWEEP = [
    # B, Hq, Hkv, W, D, ps, window, fills
    (2, 4, 4, 16, 16, 4, None, [5, 16]),      # full + exactly-full ring
    (3, 4, 2, 32, 16, 8, None, [3, 20, 40]),  # GQA, wrap past W
    (2, 8, 2, 16, 16, 4, 8, [12, 30]),        # sliding window, wrapped
    (2, 4, 1, 24, 32, 4, None, [0, 7]),       # MQA, empty ring row
]


@pytest.mark.parametrize("case", SWEEP)
def test_paged_ref_matches_dense_ref(case):
    """The paged oracle under an arbitrary page placement must equal the
    dense oracle on the gathered ring view — the page table is pure
    indirection, never semantics."""
    B, Hq, Hkv, W, D, ps, window, fills = case
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    kc = jax.random.normal(ks[1], (B, Hkv, W, D))
    vc = jax.random.normal(ks[2], (B, Hkv, W, D))
    kn = jax.random.normal(ks[3], (B, Hkv, 1, D))
    vn = jax.random.normal(ks[4], (B, Hkv, 1, D))
    pc = jnp.concatenate([ring_pos(1, W, f) for f in fills])
    pos = jnp.asarray(fills, jnp.int32)
    want = decode_attention_ref(q, kc, vc, pc, kn, vn, pos, window=window)
    ka, va, pa, pt = paged_view(kc, vc, pc, ps)
    out, ok, ov, op = decode_attention_paged_ref(
        q, ka, va, pa, kn, vn, pos, pt, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[0]),
                               atol=1e-6, rtol=1e-6)
    # the gathered arena equals the dense updated cache bit-for-bit
    kd = ok[pt].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, W, D)
    vd = ov[pt].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, W, D)
    pd = op[pt].reshape(B, W)
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(vd), np.asarray(want[2]))
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(want[3]))


@pytest.mark.parametrize("case", SWEEP)
def test_paged_pallas_matches_paged_ref(case):
    """Fused paged kernel (interpret mode) vs the paged jnp oracle: the
    scalar-prefetched page table must steer every block to the same
    physical page the oracle scatters/gathers."""
    B, Hq, Hkv, W, D, ps, window, fills = case
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    kc = jax.random.normal(ks[1], (B, Hkv, W, D))
    vc = jax.random.normal(ks[2], (B, Hkv, W, D))
    kn = jax.random.normal(ks[3], (B, Hkv, 1, D))
    vn = jax.random.normal(ks[4], (B, Hkv, 1, D))
    pc = jnp.concatenate([ring_pos(1, W, f) for f in fills])
    pos = jnp.asarray(fills, jnp.int32)
    ka, va, pa, pt = paged_view(kc, vc, pc, ps, perm_seed=3)
    got = decode_attention(q, ka, va, pa, kn, vn, pos, window=window,
                           impl="pallas", page_table=pt)
    want = decode_attention(q, ka, va, pa, kn, vn, pos, window=window,
                            impl="xla", page_table=pt)
    for g, w, name in zip(got, want, ["out", "k", "v", "pos"]):
        ga, wa = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if name != "out":       # null page content is garbage by contract
            ga, wa = ga[1:], wa[1:]
        np.testing.assert_allclose(ga, wa, atol=1e-5, rtol=1e-5,
                                   err_msg=name)


def test_paged_inactive_row_is_nulled():
    """pos = -1 rows (idle serve slots) carry all-null tables: their write
    lands in the null page, whose stored positions stay -1, and active
    rows are unaffected."""
    B, Hq, Hkv, W, D, ps = 3, 4, 2, 16, 16, 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    kc = jax.random.normal(ks[1], (B, Hkv, W, D))
    vc = jax.random.normal(ks[2], (B, Hkv, W, D))
    kn = jax.random.normal(ks[3], (B, Hkv, 1, D))
    vn = jax.random.normal(ks[4], (B, Hkv, 1, D))
    fills = [6, -1, 11]
    pc = jnp.concatenate([ring_pos(1, W, max(f, 0)) for f in fills])
    ka, va, pa, pt = paged_view(kc, vc, pc, ps, perm_seed=5)
    pt = pt.at[1].set(P.PAGE_NULL)           # idle row: all-null table
    pos = jnp.asarray(fills, jnp.int32)
    for impl in ["xla", "pallas"]:
        out, ok, ov, op = decode_attention(q, ka, va, pa, kn, vn, pos,
                                           impl=impl, page_table=pt)
        assert np.all(np.asarray(op[P.PAGE_NULL]) == -1), impl
        # active rows must equal their dense single-row references
        for b in (0, 2):
            want, *_ = decode_attention_ref(
                q[b:b + 1], kc[b:b + 1], vc[b:b + 1], pc[b:b + 1],
                kn[b:b + 1], vn[b:b + 1], jnp.int32(fills[b]))
            np.testing.assert_allclose(np.asarray(out[b:b + 1], np.float32),
                                       np.asarray(want, np.float32),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"{impl} row {b}")


WALK = {
    # Hkv, group, D, ps, n_ptes, window, fills (-1 = inactive), shared
    # prefix page, cache dtype
    "depths": (5, 3, 64, 8, 6, None, [3, 17, 30, 46], False, jnp.float32),
    "fresh-page": (2, 4, 32, 8, 4, None, [8, 16, 0], False, jnp.float32),
    "budget-end": (1, 1, 32, 8, 4, None, [31, 5], False, jnp.float32),
    "inactive": (5, 3, 64, 8, 4, None, [10, -1, 25, -1], False,
                 jnp.float32),
    "shared-prefix": (8, 4, 128, 8, 4, None, [12, 20], True, jnp.bfloat16),
    "window-wrapped": (2, 3, 32, 8, 2, 16, [21, 40, 9], False, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(WALK))
def test_paged_kernel_walks_live_pages(case):
    """The paged kernel walks each row's live pages only and stores only
    the row it writes.  Tables hold pages up to each row's last live page
    (all of them once the ring wraps) and the null page past it, as the
    serve pool provisions them.  Against the paged oracle: the outputs
    match, every arena row other than each row's write slot is
    bit-identical to the input, the written rows hold the new K/V, and
    the null page's positions stay -1."""
    Hkv, group, D, ps, n_ptes, window, fills, shared, dt = WALK[case]
    B, W = len(fills), n_ptes * ps
    n_pages = 1 + B * n_ptes
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hkv * group, 1, D), dt)
    ka = jax.random.normal(ks[1], (n_pages, Hkv, ps, D), dt)
    va = jax.random.normal(ks[2], (n_pages, Hkv, ps, D), dt)
    kn = jax.random.normal(ks[3], (B, Hkv, 1, D), dt)
    vn = jax.random.normal(ks[4], (B, Hkv, 1, D), dt)
    rng = np.random.default_rng(7)
    free = list(1 + rng.permutation(n_pages - 1))
    pt = np.full((B, n_ptes), P.PAGE_NULL, np.int32)
    pa = np.full((n_pages, ps), -1, np.int32)
    for b, f in enumerate(fills):
        if f < 0:
            continue
        ring = np.asarray(ring_pos(1, W, f)[0]).reshape(n_ptes, ps)
        for t in range(min(f, W - 1) // ps + 1):
            pt[b, t] = pt[0, 0] if shared and b == 1 and t == 0 \
                else free.pop()
            pa[pt[b, t]] = ring[t]
    if shared:
        assert pt[1, 0] == pt[0, 0] and min(fills) >= ps
    pos = jnp.asarray(fills, jnp.int32)
    args = (q, ka, va, jnp.asarray(pa), kn, vn, pos)
    got = decode_attention(*args, window=window, impl="pallas",
                           page_table=jnp.asarray(pt))
    want = decode_attention(*args, window=window, impl="xla",
                            page_table=jnp.asarray(pt))

    active = [b for b, f in enumerate(fills) if f >= 0]
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[0], np.float32)[active],
                               np.asarray(want[0], np.float32)[active],
                               atol=tol, rtol=tol)
    widx = np.mod(np.asarray(fills), W)
    written = np.zeros((n_pages, ps), bool)
    written[pt[np.arange(B), widx // ps], widx % ps] = True
    kept = ~written[:, None, :, None]
    for new_arena, arena, rows in ((got[1], ka, kn), (got[2], va, vn)):
        new_arena, arena = np.asarray(new_arena), np.asarray(arena)
        np.testing.assert_array_equal(np.where(kept, new_arena, 0),
                                      np.where(kept, arena, 0))
        for b in active:
            np.testing.assert_array_equal(
                new_arena[pt[b, widx[b] // ps], :, widx[b] % ps],
                np.asarray(rows)[b, :, 0])
    assert np.all(np.asarray(got[3][P.PAGE_NULL]) == -1)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


# ------------------------------------------------- pool tree operations ----

TINY = dict(name="tiny-paged", family="dense", num_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
            dtype="float32")
DENSE = ModelConfig(**TINY)
HYBRID = ModelConfig(**{**TINY, "pattern": (("swa", "dense"),
                                            ("full", "dense")),
                        "window": 8})


@pytest.mark.parametrize("cfg", [DENSE, HYBRID], ids=["full", "swa+full"])
def test_pool_insert_extract_scrub_roundtrip(cfg):
    """Donate a prefill into the pool, gather it back out bit-for-bit,
    then scrub: the freed pages' validity planes return to -1 while
    other sequences' pages are untouched."""
    budget, ps, n_slots = 16, 4, 3
    mgr = PagedCacheManager(cfg, n_slots, budget, page_size=ps)
    params = M.init_params(cfg, KEY)
    prefill = make_prefill_step(cfg)
    toks = jax.random.randint(KEY, (1, 7), 0, cfg.vocab)
    _, one = prefill(params, toks)
    one = align_prefill_cache(cfg, one, 7, target_len=budget)
    blocks = P.ring_to_page_blocks(cfg, one, ps)

    assert mgr.admit_pages(1, 7)
    ids = mgr.table_ids(1)
    cache = P.insert_pages(cfg, mgr.cache, blocks, ids, jnp.int32(1))
    back = P.extract_pages(cfg, cache, ids, jnp.int32(1))
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(blocks)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    scrubbed = P.scrub_pages(cfg, cache, ids)
    for gi, (kinds, _) in enumerate(M.cache_layout(cfg)):
        for pi, kind in enumerate(kinds):
            leaf = scrubbed["groups"][gi][pi]
            if kind in M.KV_KINDS:
                held = [int(p) for p in ids[kind] if p != P.PAGE_NULL]
                assert held, kind
                # every page the slot held is invalid again
                assert np.all(np.asarray(leaf.pos)[:, held] == -1), kind


def test_pool_sizing_assertions():
    with pytest.raises(AssertionError):   # page_size must divide W
        PagedCacheManager(DENSE, 2, 18, page_size=4)
    with pytest.raises(AssertionError):   # one budget-length seq must fit
        PagedCacheManager(DENSE, 2, 16, page_size=4, pool_pages=3)


# ------------------------------------------------- engine: paged serving ---

def lockstep_single(cfg, params, prompt, max_new, budget,
                    prefill_impl="xla"):
    """The dense single-request oracle (identical to the serve-engine
    test's): prefill → align → scalar-pos decode loop, greedy."""
    prefill = make_prefill_step(dataclasses.replace(cfg,
                                                    attn_impl=prefill_impl))
    decode = make_decode_step(cfg)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = prefill(params, toks)
    cache = align_prefill_cache(cfg, cache, len(prompt), target_len=budget)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(out) < max_new:
        logits, cache, _ = decode(params, cache,
                                  jnp.asarray([[out[-1]]], jnp.int32),
                                  jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def mk_trace(vocab, spec):
    rng = np.random.default_rng(17)
    return [Request(i, [int(t) for t in rng.integers(0, vocab, L)],
                    n, arrival=a)
            for i, (L, n, a) in enumerate(spec)]


TRACE = [(5, 4, 0), (9, 7, 0), (3, 2, 1), (7, 5, 3), (4, 6, 4), (6, 3, 8)]


@pytest.mark.parametrize("cfg", [DENSE, HYBRID], ids=["full", "swa+full"])
def test_paged_engine_matches_lockstep_xla(cfg):
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, TRACE)
    eng = ServeEngine(cfg, params, n_slots=3, budget=16, paged=True,
                      page_size=4)
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 16)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    # full provision: nothing should ever have been preempted
    assert eng.stats["preemptions"] == 0


def test_paged_engine_matches_lockstep_pallas():
    """Fused paged decode kernel (interpret mode) under mixed-depth
    traffic — the page table rides the scalar-prefetch plane."""
    cfg = dataclasses.replace(HYBRID, attn_impl="pallas")
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, [(5, 4, 0), (9, 6, 1), (3, 3, 2), (7, 5, 4)])
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=4, prefill_impl="xla")
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 16)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"


def test_paged_engine_preemption_preserves_streams():
    """Oversubscribed pool: admissions outpace the arena, sequences are
    preempted (KV swapped out, pages freed) and resumed — and every
    stream still equals the uninterrupted lockstep oracle."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    rng = np.random.default_rng(7)
    reqs = [Request(0, [int(t) for t in rng.integers(0, 128, 4)], 12,
                    arrival=0),
            Request(1, [int(t) for t in rng.integers(0, 128, 4)], 12,
                    arrival=0),
            Request(2, [int(t) for t in rng.integers(0, 128, 3)], 4,
                    arrival=2)]
    eng = ServeEngine(cfg, params, n_slots=3, budget=16, paged=True,
                      page_size=4, pool_pages=5)
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 16)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    assert eng.stats["preemptions"] > 0, \
        "trace was meant to exercise preemption"
    assert eng.stats["swap_ins"] == eng.stats["preemptions"]
    # conservation after the trace drained: everything back in the pool
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind
    # the arena really is smaller than the dense standing cache
    dense_bytes = P.kv_resident_bytes(
        M.cache_init(cfg, eng.n_slots, eng.budget))
    assert eng.cache_mgr.resident_bytes() < dense_bytes


def test_paged_engine_page_accounting():
    """Pages held while serving track exactly the written positions of
    the active sequences (lazy growth, no budget-shaped provisioning)."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=4)
    seq = eng.submit(Request(0, [1, 2, 3], 6))
    eng.step()           # prefill: 3 positions → 1 page; decode grows
    held = eng.cache_mgr.pages_held()["full"]
    assert held == 1 or held == 2  # +1 if the first decode page-crossed
    while not eng.done:
        eng.step()
    eng.finish()
    assert eng.cache_mgr.pages_held()["full"] == 0


@pytest.mark.parametrize("cfg,buckets,live,budget", [
    # one request, prompt 3, 8 new tokens: 7 decode steps at positions
    # 3..9 over 4-position pages.  Full ring (W 16, 4 pages): the row
    # walks 1 + 4·2 + 2·3 = 15 pages; at full width the idle row walks
    # page 0 once a step (+7).  Sliding window (W 8, 2 pages): 1 + 4·2,
    # then the wrapped ring keeps both pages live (+2·2), idle +7.
    (DENSE, False, 15 + 7, 7 * 2 * 4),
    (DENSE, True, 15, 7 * 1 * 4),
    (HYBRID, False, (15 + 7) + (13 + 7), 7 * 2 * 4 + 7 * 2 * 2),
], ids=["full-width", "packed", "swa+full"])
def test_decode_page_counters(cfg, buckets, live, budget):
    """``decode_pages_live`` / ``decode_pages_budget`` count the pages the
    paged decode kernel walks and the pages its grid spans, step by
    step, summed over the cache kinds."""
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=4, buckets=buckets)
    eng.submit(Request(0, [1, 2, 3], 8))
    while not eng.done:
        eng.step()
    eng.finish()
    assert eng.stats["decode_steps"] == 7
    assert eng.stats["decode_pages_live"] == live
    assert eng.stats["decode_pages_budget"] == budget


def test_paged_decode_keeps_kernel_name():
    """The paged decode step lowers with a computation named
    ``_decode_attention``: the benchmark finds the kernel's device time
    by that name (``^%_decode_attention\\b``)."""
    import re
    cfg = dataclasses.replace(DENSE, attn_impl="pallas")
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=4)
    i32 = jnp.int32
    step = make_decode_step(cfg)
    hlo = jax.jit(step).lower(
        params, eng.cache_mgr.cache, jnp.zeros((2, 1), i32),
        jnp.full((2,), -1, i32)).as_text(dialect="hlo")
    assert re.search(r"^%?_decode_attention\b\S* \{$", hlo, re.M)
