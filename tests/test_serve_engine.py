"""Continuous-batching serve engine: staggered arrivals must produce the
exact token streams of running each request alone through the lockstep
prefill→decode path — the end-to-end proof that per-sequence ring
positions, slot packing, and slot reuse never leak state between
requests.  Plus unit coverage of the scheduler and the cache slot
insert/extract helpers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.sharding import ShardCtx
from repro.models import model as M
from repro.models.model import ModelConfig
from repro.serve.engine import (BatchedCacheManager, Request, SlotScheduler,
                                ServeEngine, Status)
from repro.serve.step import (align_prefill_cache, cache_slot_extract,
                              cache_slot_insert, make_align_step,
                              make_decode_step, make_prefill_step)

KEY = jax.random.PRNGKey(5)


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="tiny-serve", family="dense", num_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
                dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


DENSE = tiny_cfg()
SWA = tiny_cfg(pattern=(("swa", "dense"),), window=6)


def lockstep_single(cfg, params, prompt, max_new, budget,
                    prefill_impl="xla"):
    """The pre-engine serving path, one request at a time: batched-of-one
    prefill → align → scalar-pos decode loop, greedy."""
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


# prompt-length / budget / arrival staggering, early finishes, more
# requests than slots (forces queueing and slot reuse)
TRACE = [(5, 4, 0), (9, 7, 0), (3, 2, 1), (7, 5, 3), (4, 6, 4), (6, 3, 8)]


@pytest.mark.parametrize("cfg", [DENSE, SWA], ids=["full", "swa-ring"])
def test_engine_matches_lockstep_xla(cfg):
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, TRACE)
    eng = ServeEngine(cfg, params, n_slots=3, budget=24)
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 24)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"
    # continuous batching actually interleaved: fewer ticks than the sum
    # of per-request decode depths
    assert eng.tick < sum(n for _, n, _ in TRACE)
    assert eng.stats["decoded_tokens"] == \
        sum(len(s) for s in streams.values()) - len(reqs)


def test_engine_matches_lockstep_pallas():
    """Fused Pallas decode (interpret mode on CPU) under mixed-depth
    traffic — per-sequence (B,) ring writes inside the kernel."""
    cfg = dataclasses.replace(SWA, attn_impl="pallas")
    params = M.init_params(cfg, KEY)
    reqs = mk_trace(cfg.vocab, [(5, 4, 0), (9, 6, 1), (3, 3, 2), (7, 5, 4)])
    eng = ServeEngine(cfg, params, n_slots=2, budget=16, prefill_impl="xla")
    streams = eng.run(reqs)
    for r in reqs:
        ref = lockstep_single(cfg, params, r.prompt, r.max_new_tokens, 16)
        assert streams[r.rid] == ref, \
            f"rid={r.rid}: {streams[r.rid]} != {ref}"


def test_engine_eos_and_single_token_budget():
    """max_new_tokens=1 retires at admission (prefill-only request); an
    eos_id stops a stream early and frees the slot."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    probe = lockstep_single(cfg, params, list(range(4)), 3, 16)
    reqs = [Request(0, list(range(4)), 1),
            Request(1, list(range(4)), 8, eos_id=probe[1]),
            Request(2, list(range(1, 6)), 4)]
    eng = ServeEngine(cfg, params, n_slots=2, budget=16)
    streams = eng.run(reqs)
    assert streams[0] == probe[:1]
    assert streams[1] == probe[:2]            # stopped by eos, not budget
    assert streams[2] == lockstep_single(cfg, params, list(range(1, 6)),
                                         4, 16)


def test_engine_profiling_lanes():
    """Admission and decode land on their own profiled lanes with the
    canonical event names (prof sees interleaving for free)."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16)
    eng.run(mk_trace(cfg.vocab, [(4, 3, 0), (5, 2, 1)]))
    admit_names = {e.name for e in eng.q_admit.events}
    decode_names = {e.name for e in eng.q_decode.events}
    assert admit_names == {"PREFILL_KERNEL", "ALIGN_CACHE", "SLOT_INSERT"}
    assert decode_names == {"DECODE_KERNEL"}


def test_scheduler_fifo_and_slot_reuse():
    s = SlotScheduler(2)
    seqs = [s.submit(Request(i, [1], 4)) for i in range(4)]
    first = s.admit()
    assert [(q.rid, slot) for q, slot in first] == [(0, 0), (1, 1)]
    assert s.admit() == [] and s.n_waiting == 2
    s.release(1)
    with pytest.raises(AssertionError):
        s.release(1)                     # double release of a free slot
    second = s.admit()
    assert [(q.rid, slot) for q, slot in second] == [(2, 1)]
    s.release(0)
    assert [(q.rid, slot) for q, slot in s.admit()] == [(3, 0)]
    assert s.n_waiting == 0 and s.n_free == 0


def test_scheduler_remove_tombstones_and_free_set():
    """remove() is O(1): the sequence is tombstoned and physically
    dropped when it surfaces at the head — it must never be admitted —
    and the free list's set mirror still catches double releases."""
    s = SlotScheduler(2)
    a, b, c = (s.submit(Request(i, [1], 4)) for i in range(3))
    assert s.remove(b)
    assert not s.remove(b)                   # already withdrawn
    assert s.n_waiting == 2
    got = s.admit()                          # b never surfaces
    assert [(q.rid, slot) for q, slot in got] == [(0, 0), (2, 1)]
    assert s.n_waiting == 0
    assert not s.remove(a)                   # bound ≠ waiting
    s.release(1)
    s.release(0)
    with pytest.raises(AssertionError):
        s.release(0)                         # double release still caught
    # lowest slot first across out-of-order releases
    d = s.submit(Request(3, [1], 4))
    assert s.pop_bind() == (d, 0)
    # preemption-style resurrection of a previously removed sequence
    s.requeue_front(b)
    assert s.peek() is b


def test_reap_cost_independent_of_retired_sequences():
    """The deadline/cancel sweep and the done check walk the *live* set:
    after N requests retire, a tick scans only the sequences still in
    flight, not every sequence ever submitted (the long-running-server
    regression: _reap used to iterate eng.sequences)."""
    class SpyDict(dict):
        def __init__(self, *a):
            super().__init__(*a)
            self.scanned = 0

        def values(self):
            self.scanned += len(self)
            return super().values()

    cfg = DENSE
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=2, budget=16)
    eng.run(mk_trace(cfg.vocab, [(4, 2, 0)] * 10))
    assert len(eng.sequences) == 10 and eng.done
    eng._live = spy = SpyDict(eng._live)
    live = eng.submit(Request(99, [3, 1, 4], 4))
    while not eng.done:
        before = spy.scanned
        eng.step()
        assert spy.scanned - before <= 1, \
            "per-tick sweep scanned retired sequences"
    eng.finish()
    assert live.status is Status.FINISHED
    assert len(eng.sequences) == 11          # history is kept


@pytest.mark.parametrize("cfg", [DENSE, SWA], ids=["full", "swa-ring"])
def test_cache_slot_insert_extract_roundtrip(cfg):
    """insert puts a B=1 cache into its slot and nothing else; extract
    returns it bit-for-bit."""
    budget = 16
    params = M.init_params(cfg, KEY)
    prefill = make_prefill_step(cfg)
    toks = jax.random.randint(KEY, (1, 7), 0, cfg.vocab)
    _, one = prefill(params, toks)
    one = align_prefill_cache(cfg, one, 7, target_len=budget)

    batched = M.cache_init(cfg, 3, budget)
    before = jax.tree.leaves(batched)
    packed = cache_slot_insert(batched, one, jnp.int32(1))

    back = cache_slot_extract(packed, jnp.int32(1))
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the other slots are untouched
    for slot in (0, 2):
        other = cache_slot_extract(packed, jnp.int32(slot))
        init = cache_slot_extract(batched, jnp.int32(slot))
        for got, want in zip(jax.tree.leaves(other), jax.tree.leaves(init)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and insert was functional (input pytree not mutated)
    for a, b in zip(before, jax.tree.leaves(batched)):
        assert a is b


def test_step_factories_cache_on_cfg_and_ctx():
    """Rebuilding steps must never retrace: the factories cache on
    (cfg, ctx) — including a non-None ShardCtx, which hashes by identity
    — so repeated calls return the *same* jitted callable."""
    cfg = DENSE
    ctx = ShardCtx(mesh=None)
    for make in (make_prefill_step, make_decode_step):
        assert make(cfg) is make(cfg)
        assert make(cfg, ctx) is make(cfg, ctx)      # the old retrace bug
        assert make(cfg, ctx) is not make(cfg)
        assert make(cfg, ShardCtx(mesh=None)) is not make(cfg, ctx)
    assert make_align_step(cfg, 7, 16) is make_align_step(cfg, 7, 16)
    # and the identical callable means the jit cache is shared: tracing a
    # rebuilt step a second time must hit the first build's cache
    probe_cfg = tiny_cfg(name="tiny-retrace")
    params = M.init_params(probe_cfg, KEY)
    toks = jnp.zeros((1, 4), jnp.int32)
    step = make_prefill_step(probe_cfg, ctx)
    step(params, toks)
    misses0 = step._cache_size()
    rebuilt = make_prefill_step(probe_cfg, ctx)
    rebuilt(params, toks)
    assert rebuilt is step and rebuilt._cache_size() == misses0, \
        "rebuilding the step retraced the jit"


def test_align_rejects_zero_target_len():
    """target_len=0 must be an error, not silently "no target" (the old
    ``target_len or seq_len`` coercion)."""
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    prefill = make_prefill_step(cfg)
    _, cache = prefill(params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(AssertionError, match="positive decode budget"):
        align_prefill_cache(cfg, cache, 4, target_len=0)
    # None still means "use the prefill length"
    out = align_prefill_cache(cfg, cache, 4, target_len=None)
    assert out["groups"][0][0].k.shape[-2] == 4


REC = tiny_cfg(name="tiny-rec", family="hybrid",
               pattern=(("rec", "dense"), ("full", "dense")),
               lru_width=32, conv_kernel=4)
SSM = tiny_cfg(name="tiny-ssm", family="ssm",
               pattern=(("ssm", "dense"), ("swa", "dense")), window=8,
               ssm_state=16, ssm_heads=4, ssm_head_dim=16, ssm_groups=1)
CHUNKED = tiny_cfg(name="tiny-chunked", pattern=(("chunked", "dense"),),
                   chunk=8)


@pytest.mark.parametrize("cfg", [DENSE, SWA, CHUNKED, REC, SSM],
                         ids=["full", "swa", "chunked", "rec-hybrid",
                              "ssm-hybrid"])
def test_cache_manager_insert_extract_roundtrip(cfg):
    """``BatchedCacheManager.extract`` ("debugging / migration") against
    ``insert`` for every cache kind — KV rings, rolling windows, chunked
    rings, and ssm/rec state caches — before it becomes the basis of the
    paged pool's page-table remaps."""
    budget = 16
    mgr = BatchedCacheManager(cfg, 3, budget)
    one = M.cache_init(cfg, 1, budget)
    # fill the batch=1 cache with recognizable non-zero leaves
    c = [0]

    def fill(a):
        c[0] += 1
        return (jnp.arange(a.size, dtype=jnp.float32)
                .reshape(a.shape) * c[0]).astype(a.dtype)

    one = jax.tree.map(fill, one)
    mgr.insert(one, 2)
    back = mgr.extract(2)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # untouched slots still carry the init state
    init = M.cache_init(cfg, 1, budget)
    for slot in (0, 1):
        other = mgr.extract(slot)
        for got, want in zip(jax.tree.leaves(other),
                             jax.tree.leaves(init)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))


def test_sequence_lifecycle_stamps():
    cfg = DENSE
    params = M.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, n_slots=1, budget=16)
    reqs = mk_trace(cfg.vocab, [(4, 3, 0), (5, 2, 0)])
    eng.run(reqs)
    s0, s1 = eng.sequences
    assert s0.status is Status.FINISHED and s1.status is Status.FINISHED
    # single slot: request 1 could only be admitted after 0 retired
    assert s1.admitted_at >= s0.finished_at
    assert s0.slot == s1.slot == 0
