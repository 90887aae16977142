"""Prefix sharing + copy-on-write over the paged KV pool.

The conformance contract: identical prompt prefixes are served from one
set of physical pages (refcounts in ``PageAllocator``, chain-hashed
full-page lookup in ``PrefixIndex``, partial prefill from the first
unshared token), sequences that diverge copy-on-write before the first
conflicting ring write, and **every stream is bit-identical to the
unshared run** — under plain serving, retire-while-shared, and
preemption — for both the xla and pallas-interpret decode paths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import model as M
from repro.models.model import ModelConfig
from repro.serve import paging as P
from repro.serve.engine import PagedCacheManager, Request, ServeEngine
from repro.serve.step import (align_prefill_cache, make_decode_step,
                              make_prefill_ext_step, make_prefill_step)

KEY = jax.random.PRNGKey(23)

TINY = dict(name="tiny-prefix", family="dense", num_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
            dtype="float32")
DENSE = ModelConfig(**TINY)
# window ≥ the shared prompts (sharing requires L ≤ W for every kind) but
# < the budget, so decode wraps the swa ring into shared pages → CoW
HYBRID = ModelConfig(**{**TINY, "pattern": (("swa", "dense"),
                                            ("full", "dense")),
                        "window": 16})


# -------------------------------------------- refcounted PageAllocator -----

@settings(max_examples=40)
@given(st.integers(3, 16),
       st.lists(st.integers(0, 4), min_size=4, max_size=30),
       st.integers(0, 2 ** 31))
def test_allocator_share_release_properties(n_pages, sizes, seed):
    """Random alloc/share/release interleavings against a reference
    refcount model: a page returns to the free list exactly when its
    refcount reaches 0, grants never overlap held pages, ``n_held``
    counts distinct pages (shared pages once), and accounting always
    conserves ``n_free + n_held == capacity``."""
    rng = np.random.default_rng(seed)
    alloc = P.PageAllocator(n_pages)
    capacity = n_pages - 1
    model = {}                                  # page → refcount oracle
    for n in sizes:
        if n <= alloc.n_free:
            got = alloc.alloc(n)
            assert got is not None and len(got) == n
            assert not set(got) & set(model), "granted a held page"
            for p in got:
                model[p] = 1
        elif n <= capacity:
            assert alloc.alloc(n) is None       # transient pressure
        if model and rng.integers(0, 2):        # share a random held page
            p = int(rng.choice(list(model)))
            alloc.share(p)
            model[p] += 1
        if model and rng.integers(0, 2):        # release a random ref
            p = int(rng.choice(list(model)))
            freed = alloc.free([p])
            model[p] -= 1
            if model[p] == 0:
                assert freed == [p], "page must free exactly at refcount 0"
                del model[p]
            else:
                assert freed == [], "freed a page others still reference"
        for p, refs in model.items():
            assert alloc.refcount(p) == refs
        assert alloc.n_held == len(model)
        assert alloc.n_free + alloc.n_held == capacity
    while model:
        p = next(iter(model))
        for _ in range(model.pop(p)):
            alloc.free([p])
    assert alloc.n_free == capacity and alloc.n_held == 0


def test_allocator_share_release_unit():
    alloc = P.PageAllocator(6)
    a, b = alloc.alloc(2)
    alloc.share(a)                              # refcount 2
    assert alloc.refcount(a) == 2 and alloc.refcount(b) == 1
    assert alloc.n_held == 2                    # shared page counts once
    assert alloc.free([a, b]) == [b]            # a survives its first free
    assert alloc.refcount(a) == 1
    assert alloc.release(a)                     # now it frees
    assert alloc.refcount(a) == 0 and alloc.n_held == 0
    with pytest.raises(AssertionError):
        alloc.free([a])                         # double-free
    with pytest.raises(AssertionError):
        alloc.share(b)                          # share of a free page


# --------------------------------------------------------- PrefixIndex -----

def test_prefix_index_chain_match_and_forget():
    idx = P.PrefixIndex(page_size=4)
    toks = list(range(10, 22))                  # 3 full pages
    idx.register(toks, [5, 7, 9])
    assert idx.match(toks) == [5, 7, 9]
    assert idx.match(toks + [99]) == [5, 7, 9]  # longer prompt, same run
    assert idx.match(toks[:7]) == [5]           # one full page only
    # a different first page breaks the chain immediately — the key of
    # page t commits to the whole prefix behind it
    assert idx.match([0] + toks[1:]) == []
    assert idx.match(toks[:3]) == []            # no full page at all
    # forgetting a middle page truncates every deeper match (the deeper
    # registration survives — its content was never written — and
    # rejoins the chain once the gap is re-registered)
    idx.forget(7)
    assert idx.match(toks) == [5]
    assert 7 not in idx and 5 in idx and 9 in idx
    idx.register(toks, [5, 11, 13])
    assert idx.match(toks) == [5, 11, 9]
    # register is idempotent: re-registering the same blocks under new
    # pages must not displace the resident ones
    idx.register(toks, [6, 12, 14])
    assert idx.match(toks) == [5, 11, 9]


def test_prefix_chain_incremental_hashing():
    """PrefixChain memoizes the running chain: re-requesting a prefix
    already walked costs zero new digests, extending hashes only the new
    full pages, and the keys agree with PrefixIndex's from-scratch
    generator — so the engine's every-tick re-match of a queued head is
    O(new pages), not O(prompt)."""
    ps = 4
    rng = np.random.default_rng(11)
    toks = [int(t) for t in rng.integers(0, 128, 40)]
    chain = P.PrefixChain(ps)
    k5 = chain.keys(toks, 5)
    assert chain.hashes == 5
    assert chain.keys(toks, 5) == k5           # re-match: zero hashing
    assert chain.hashes == 5
    k10 = chain.keys(toks, 10)
    assert chain.hashes == 10                  # extension: new pages only
    assert k10[:5] == k5
    assert k10 == list(P.PrefixIndex(ps).keys(toks, 10))
    # n_pages caps at the full pages available; None means all of them
    assert chain.keys(toks) == k10
    assert chain.hashes == 10


# ------------------------------------- partial prefill ≡ full prefill ------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cfg", [DENSE, HYBRID], ids=["full", "swa+full"])
def test_prefill_ext_matches_full_prefill(cfg, impl):
    """Resuming a prefill mid-prompt from a bit-exact prefix cache must
    reproduce the one-shot prefill exactly: same last-token logits, same
    collected cache bits — the property that makes shared-prefix streams
    indistinguishable from unshared ones.  Pinned per impl: the pallas
    flash path runs the ext step with explicit position planes, which
    must be bit-identical to its own one-shot prefill (same ``(S,
    block_kv)`` partition ⇒ masked contributions are exact no-ops)."""
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    params = M.init_params(cfg, KEY)
    prefill = make_prefill_step(cfg)
    prefill_ext = make_prefill_ext_step(cfg)
    L, s = 11, 8
    toks = jax.random.randint(KEY, (1, L), 0, cfg.vocab)
    logits_full, cache_full = prefill(params, toks)

    def cut(c):
        if not isinstance(c, M.A.KVCache):
            return c
        return M.A.KVCache(c.k[..., :s, :], c.v[..., :s, :],
                           c.pos[..., :s])

    prefix = {"groups": [tuple(cut(c) for c in g)
                         for g in cache_full["groups"]]}
    logits_ext, cache_ext = prefill_ext(params, toks[:, s:], prefix)
    np.testing.assert_array_equal(np.asarray(logits_ext),
                                  np.asarray(logits_full))
    for got, want in zip(jax.tree.leaves(cache_ext),
                         jax.tree.leaves(cache_full)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------- engine oracles ------

def lockstep_single(cfg, params, prompt, max_new, budget):
    """The unshared single-request oracle (prefill → align → decode)."""
    prefill = make_prefill_step(dataclasses.replace(cfg, attn_impl="xla"))
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


def sys_prompt(n, seed=3):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, 128, n)]


def check_streams(cfg, params, eng, reqs, budget):
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens,
                              budget)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    return streams


# --------------------------------------------------- CoW divergence --------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_cow_divergence_streams_bit_identical(impl):
    """Two sequences share a 2-page prefix, diverge, and decode far
    enough to wrap the swa ring back into the shared pages: the first
    conflicting write must copy-on-write, and both streams must equal
    their unshared oracles bit-for-bit."""
    cfg = dataclasses.replace(HYBRID, attn_impl=impl)
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)                          # 2 full pages at ps=4
    reqs = [Request(0, pre + [5, 9], 13, arrival=0),
            Request(1, pre + [7, 3], 13, arrival=0)]
    eng = ServeEngine(cfg, params, n_slots=2, budget=24, paged=True,
                      page_size=4, prefill_impl="xla")
    check_streams(cfg, params, eng, reqs, 24)
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["shared_tokens"] == 8
    assert eng.stats["cow_copies"] >= 1, \
        "the trace was meant to wrap into a shared page"
    # everything drained back into the pool
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


def test_sharing_stays_enabled_with_pallas_prefill():
    """Partial prefill now runs the flash kernel with explicit position
    planes, so an effective pallas prefill keeps sharing ON (the PR 5
    auto-disable is gone): shared streams must be bit-identical to the
    unshared pallas engine (same kernel, same block partition — masked
    contributions are exact no-ops) and to the XLA lockstep oracle."""
    cfg = dataclasses.replace(DENSE, attn_impl="pallas")
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)                          # 2 full pages at ps=4
    mk = lambda: [Request(0, pre + [5, 9], 8, arrival=0),
                  Request(1, pre + [7, 3], 8, arrival=0)]
    eng = ServeEngine(cfg, params, n_slots=2, budget=24, paged=True,
                      page_size=4)
    assert eng.cache_mgr.sharing, \
        "pallas prefill must no longer auto-disable prefix sharing"
    shared = check_streams(cfg, params, eng, mk(), 24)
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["shared_tokens"] == 8
    unshared_eng = ServeEngine(cfg, params, n_slots=2, budget=24,
                               paged=True, page_size=4,
                               prefix_sharing=False)
    assert unshared_eng.run(mk()) == shared


def test_sharing_disabled_matches_and_pays_full_prefill():
    """The prefix_sharing=False baseline (PR 4 semantics): identical
    streams, but every prompt token is prefilled and no pages shared."""
    cfg = HYBRID
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)
    reqs = [Request(0, pre + [5, 9], 8, arrival=0),
            Request(1, pre + [7, 3], 8, arrival=0)]
    eng = ServeEngine(cfg, params, n_slots=2, budget=24, paged=True,
                      page_size=4, prefix_sharing=False)
    check_streams(cfg, params, eng, reqs, 24)
    assert eng.stats["prefix_hits"] == 0
    assert eng.stats["shared_tokens"] == 0
    assert eng.stats["prefill_tokens"] == sum(len(r.prompt) for r in reqs)


# ----------------------------------------------- retire while shared -------

def test_release_slot_never_reports_shared_pages():
    """Manager-level scrub gate: release of one sharer reports (for
    scrubbing) only pages that reached refcount 0 — a freed-but-shared
    page is impossible to scrub because release never names it."""
    mgr = PagedCacheManager(DENSE, 2, 16, page_size=4)
    pre = sys_prompt(8)
    assert mgr.admit_pages(0, len(pre) + 1)
    mgr.register_prefix(0, pre + [42])
    shared_toks, ids = mgr.match_prefix(pre + [7])
    assert shared_toks == 8
    assert mgr.admit_pages(1, 9, shared=ids)
    shared_pages = {int(p) for p in ids["full"]}
    # slot 0 retires: its exclusive tail page frees, the shared prefix
    # pages survive at refcount 1 and stay registered
    freed = mgr.release_slot(0)
    reported = {int(p) for p in freed["full"] if p != P.PAGE_NULL}
    assert not reported & shared_pages, \
        "release reported a still-shared page for scrubbing"
    for p in shared_pages:
        assert mgr.alloc["full"].refcount(p) == 1
        assert p in mgr.prefix["full"]
    # slot 1 retires: now they free (and deregister)
    freed = mgr.release_slot(1)
    assert shared_pages <= {int(p) for p in freed["full"]}
    for p in shared_pages:
        assert p not in mgr.prefix["full"]
    assert mgr.alloc["full"].n_held == 0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_retire_while_shared_keeps_sharer_pages(impl):
    """Engine-level: the registering sequence finishes first while its
    prefix pages are still mapped by a live sharer — the survivor's
    stream must stay bit-exact (the retirement scrub must not touch the
    shared pages) and its prefix pages must still hold valid positions
    on device."""
    cfg = dataclasses.replace(DENSE, attn_impl=impl)
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(4)                          # 1 full page at ps=4
    reqs = [Request(0, pre + [5], 2, arrival=0),   # finishes early
            Request(1, pre + [9], 10, arrival=0)]  # keeps decoding
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=4, prefill_impl="xla")
    for r in reqs:
        eng.submit(r)
    while not eng.sequences[0].status.value == "finished":
        eng.step()
    assert eng.stats["prefix_hits"] == 1
    # survivor still active: its shared prefix page must be valid
    survivor = eng.sequences[1]
    assert survivor.slot >= 0
    page = int(eng.cache_mgr.tables["full"][survivor.slot, 0])
    assert page != P.PAGE_NULL
    eng.finish()
    for gi, (kinds, _) in enumerate(M.cache_layout(cfg)):
        for pi, kind in enumerate(kinds):
            if kind == "full":
                leaf = eng.cache_mgr.cache["groups"][gi][pi]
                np.testing.assert_array_equal(
                    np.asarray(leaf.pos)[:, page],
                    np.broadcast_to(np.arange(4), (leaf.pos.shape[0], 4)))
    while not eng.done:
        eng.step()
    eng.finish()
    ref = lockstep_single(cfg, params, reqs[1].prompt, 10, 16)
    assert list(survivor.out_tokens) == ref


# ------------------------------------------- preemption under sharing ------

def test_preemption_under_sharing_preserves_streams():
    """Oversubscribed pool with shared prefixes in flight: preemption
    (swap-out must not evict pages another sequence reads) and
    resumption keep every stream bit-identical to the unshared
    oracle."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(4)
    reqs = [Request(0, pre + [5, 9], 10, arrival=0),
            Request(1, pre + [7, 3], 10, arrival=0),
            Request(2, pre + [2, 8], 8, arrival=1)]
    eng = ServeEngine(cfg, params, n_slots=3, budget=16, paged=True,
                      page_size=4, pool_pages=7)
    check_streams(cfg, params, eng, reqs, 16)
    assert eng.stats["preemptions"] > 0, \
        "trace was meant to exercise preemption"
    assert eng.stats["prefix_hits"] > 0
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


# --------------------------------------------------- page accounting -------

def test_shared_pages_counted_once():
    """N sequences over one system prompt occupy the shared pages once:
    peak distinct pages held is strictly below the unshared footprint,
    with identical streams."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)                          # 2 shared pages
    reqs = [Request(i, pre + [10 + i], 4, arrival=0) for i in range(4)]

    def serve(sharing):
        eng = ServeEngine(cfg, params, n_slots=4, budget=16, paged=True,
                          page_size=4, prefix_sharing=sharing)
        for r in reqs:
            eng.submit(r)
        peak = 0
        while not eng.done:
            eng.step()
            peak = max(peak, sum(eng.cache_mgr.pages_held().values()))
        eng.finish()
        return {s.rid: list(s.out_tokens) for s in eng.sequences}, peak

    streams_off, peak_off = serve(False)
    streams_on, peak_on = serve(True)
    assert streams_on == streams_off
    # 4 sequences × 2 shared pages collapse to one resident copy
    assert peak_on <= peak_off - 2 * (len(reqs) - 1)


# ------------------------------------- sharing-aware victim scoring --------

def test_exclusive_pages_counts_only_refcount_one():
    """``exclusive_pages`` is the preemption victim score's dominant
    term: only pages the slot holds at refcount 1 count — registration
    alone is not sharing, a mapped-by-reference prefix contributes
    nothing, and a slot holding *only* shared pages scores 0 (evicting
    it would free no pool pages at all)."""
    mgr = PagedCacheManager(DENSE, 3, 16, page_size=4)
    pre = sys_prompt(8)
    assert mgr.admit_pages(0, 9)                 # 3 pages, all exclusive
    mgr.register_prefix(0, pre + [42])           # registers 2 full pages
    assert mgr.exclusive_pages(0) == 3           # registered ≠ shared
    shared_toks, ids = mgr.match_prefix(pre + [7])
    assert shared_toks == 8
    assert mgr.admit_pages(1, 9, shared=ids)     # 2 by reference + 1 fresh
    assert mgr.exclusive_pages(0) == 1
    assert mgr.exclusive_pages(1) == 1
    assert mgr.admit_pages(2, 8, shared=ids)     # fully shared mapping
    assert mgr.exclusive_pages(2) == 0


def test_victim_prefers_exclusive_page_holder():
    """The old youngest-first policy would evict the youngest sequence
    even when its pages are mostly shared (freeing ~nothing); the
    sharing-aware score must pick the holder of the most exclusive
    pages instead — and the evicted sequence must still resume
    bit-exactly."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)
    uniq = sys_prompt(12, seed=9)
    reqs = [Request(0, pre, 12, arrival=0),      # registers the prefix
            Request(1, uniq, 12, arrival=0),     # every page exclusive
            Request(2, pre + [3], 12, arrival=1)]  # youngest, shares pre
    eng = ServeEngine(cfg, params, n_slots=3, budget=24, paged=True,
                      page_size=4)
    for r in reqs[:2]:
        eng.submit(r)
    eng.step()
    eng.submit(reqs[2])
    eng.step()
    eng.step()
    by_rid = {s.rid: s for s in eng.sequences}
    mgr = eng.cache_mgr
    assert mgr.exclusive_pages(by_rid[1].slot) > \
        mgr.exclusive_pages(by_rid[2].slot)
    victim = eng._preempt_one()
    assert victim is by_rid[1], \
        "victim must be the exclusive-page holder, not the youngest"
    assert eng.stats["preemptions"] == 1
    while not eng.done:
        eng.step()
    eng.finish()
    assert eng.stats["swap_ins"] == 1
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 24)
        assert list(by_rid[r.rid].out_tokens) == ref, r.rid
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


# ------------------------------------- preempt → resume stays shared -------

@settings(max_examples=6, deadline=None)
@given(st.integers(2, 4), st.integers(0, 127))
def test_preempt_resume_keeps_prefix_pages_shared(oom_tick, tail):
    """Property (the tentpole's core invariant): a preempt → resume
    cycle of a sequence holding shared prefix pages must re-attach to
    the *same* physical pages by reference — after the swap-in the
    shared pages sit at exactly refcount 2 (donor + resumed sharer, the
    preemption pins dropped) and both slots' tables lead with the same
    page run.  Before sharing-aware resume, swap-in restored the whole
    row from the blob into fresh exclusive pages, leaving the donor's
    copy at refcount 1 and the pool holding a duplicate."""
    from repro.ft.inject import FaultPlan
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)                          # 2 shared pages at ps=4
    eng = ServeEngine(cfg, params, n_slots=2, budget=24, paged=True,
                      page_size=4,
                      fault_plan=FaultPlan(growth_oom={oom_tick}))
    donor = eng.submit(Request(0, pre, 8, arrival=0))
    eng.step()
    shared = {kind: [int(p) for p in
                     eng.cache_mgr.tables[kind][donor.slot][:2]]
              for kind in eng.cache_mgr.widths}
    sharer = eng.submit(Request(1, pre + [tail], 8, arrival=1))
    checked = False
    while not eng.done:
        eng.step()
        if not checked and eng.stats["swap_ins"] == 1:
            checked = True
            for kind, pages in shared.items():
                alloc = eng.cache_mgr.alloc[kind]
                for p in pages:
                    assert alloc.refcount(p) == 2, \
                        (kind, p, alloc.refcount(p))
                for s in eng._slot_seq:
                    row = eng.cache_mgr.tables[kind][s][:2]
                    assert [int(q) for q in row] == pages, (kind, s)
    eng.finish()
    assert eng.stats["preemptions"] == 1 and checked
    assert eng.stats["resume_shared_tokens"] >= 8
    assert list(donor.out_tokens) == \
        lockstep_single(cfg, params, pre, 8, 24)
    assert list(sharer.out_tokens) == \
        lockstep_single(cfg, params, pre + [tail], 8, 24)
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


# ------------------------------------------- decode-page fan-out -----------

def test_fanout_decode_pages_shared_streams_exact():
    """Agentic fan-out: continuations whose prompt extends an earlier
    request's prompt *and output* share past the prompt — the seed's
    decode-produced page was registered when it closed, so a 13-token
    continuation prompt maps 12 tokens by reference (3 pages: 2 prompt
    + 1 decode-produced) and prefills one.  Streams must equal the
    unshared oracle bit-for-bit (decode-written K/V ≡ prefill-written
    K/V)."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8, seed=5)
    seed_out = lockstep_single(cfg, params, pre, 12, 24)
    stem = pre + seed_out[:4]        # prompt + one closed decode page
    reqs = [Request(0, pre, 12, arrival=0),
            Request(1, stem + [3], 8, arrival=5),
            Request(2, stem + [11], 8, arrival=5)]
    eng = ServeEngine(cfg, params, n_slots=3, budget=24, paged=True,
                      page_size=4)
    check_streams(cfg, params, eng, reqs, 24)
    by_rid = {s.rid: s for s in eng.sequences}
    # shared span exceeds the seed's 8-token prompt: decode pages shared
    assert by_rid[1].shared_tokens == 12
    assert by_rid[2].shared_tokens == 12
    assert eng.stats["prefix_hits"] == 2
    assert eng.stats["shared_tokens"] == 24
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind


# -------------------------------------------- release on failure -----------

@settings(max_examples=12)
@given(st.integers(1, 2),
       st.sampled_from(["cancel", "deadline", "nan"]),
       st.integers(0, 127))
def test_failure_releases_shared_prefix_exactly(kill_after, mode, tail):
    """Property: killing a sequence that holds shared prefix pages — by
    client cancellation, deadline expiry, or NaN quarantine — returns
    the allocators, free lists, and prefix indexes *exactly* to their
    pre-admission state: shared pages decref back to the donor's count,
    the sharer's exclusive pages free, and no registration leaks."""
    from repro.core.errors import Code
    from repro.ft.inject import FaultPlan
    from repro.serve.engine import Status

    cfg = DENSE
    params = M.init_params(cfg, KEY)
    pre = sys_prompt(8)                          # 1 full shared page at ps=8
    plan = FaultPlan(nan_at={(1, 1 + kill_after)}) if mode == "nan" \
        else None
    # page_size=8: the donor can never close a decode-produced page
    # inside the 16-position budget, so the pre-admission snapshot stays
    # the exact expected state (with ps=4 the donor's own decode-page
    # registration at pos 12 would legitimately extend the index
    # mid-window — that behaviour has its own tests)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, paged=True,
                      page_size=8, fault_plan=plan)
    donor = eng.submit(Request(0, pre, 8))
    eng.step()                                   # donor settles in page 2
    snap_alloc = {k: a.state() for k, a in eng.cache_mgr.alloc.items()}
    snap_index = {k: i.state() for k, i in eng.cache_mgr.prefix.items()}

    deadline = kill_after if mode == "deadline" else None
    sharer = eng.submit(Request(1, pre + [tail], 7,
                                deadline_ticks=deadline))
    for i in range(kill_after + 3):
        eng.step()
        if mode == "cancel" and i + 1 == kill_after:
            sharer.cancel()
        if sharer.status is Status.FAILED:
            break
    assert sharer.status is Status.FAILED, (mode, sharer.status)
    assert sharer.error.code is {
        "cancel": Code.CANCELLED, "deadline": Code.DEADLINE_EXCEEDED,
        "nan": Code.NUMERIC_FAULT}[mode]
    assert eng.stats["prefix_hits"] == 1
    assert {k: a.state() for k, a in eng.cache_mgr.alloc.items()} \
        == snap_alloc, "allocator state did not return to pre-admission"
    assert {k: i.state() for k, i in eng.cache_mgr.prefix.items()} \
        == snap_index, "prefix index did not return to pre-admission"

    # the donor is unperturbed: stream equals its oracle, pool drains
    while not eng.done:
        eng.step()
    eng.finish()
    assert donor.status is Status.FINISHED
    assert list(donor.out_tokens) == lockstep_single(cfg, params, pre,
                                                     8, 16)
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, kind
