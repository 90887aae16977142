"""Fault-tolerant serving: deadlines, cancellation, poison isolation,
and the deterministic fault-injection harness.

The conformance contract (ISSUE: "chaos conformance"): under *any*
seed-driven :class:`~repro.ft.inject.FaultPlan`, (1) failed requests
terminate with the expected structured ``Code`` — never a bare string,
never a crash of ``step()``; (2) every page returns to the free list
refcount-exact and the prefix index forgets every registration; and
(3) surviving sequences' streams are **byte-identical** to the
fault-free lockstep oracle, while failed sequences' partial streams are
clean prefixes of theirs — for both the xla and pallas-interpret decode
paths.  Plus targeted unit scenarios for each failure path and the
virtual-clock straggler-detection loop against the supervisor.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.errors import Code, ReproError
from repro.ft.inject import (FaultPlan, InjectedFault, LaneFault,
                             VirtualClock, chaos_run)
from repro.ft.supervisor import Supervisor
from repro.models import model as M
from repro.models.model import ModelConfig
from repro.serve.engine import Request, ServeEngine, Status
from repro.serve.engine.engine import PER_REQUEST_CODES
from repro.serve.step import (align_prefill_cache, make_decode_step,
                              make_prefill_step)

KEY = jax.random.PRNGKey(29)

TINY = dict(name="tiny-fault", family="dense", num_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
            dtype="float32")
DENSE = ModelConfig(**TINY)
# chaos runs the hybrid config: swa+full exercises multi-kind page
# accounting on every failure-release path
HYBRID = ModelConfig(**{**TINY, "name": "tiny-fault-hyb",
                        "pattern": (("swa", "dense"), ("full", "dense")),
                        "window": 16})

PARAMS = {}


def params_for(cfg):
    if cfg.name not in PARAMS:
        PARAMS[cfg.name] = M.init_params(cfg, KEY)
    return PARAMS[cfg.name]


def lockstep_single(cfg, params, prompt, max_new, budget):
    """Fault-free single-request oracle (prefill → align → decode)."""
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


BUDGET = 32


def mk_trace():
    rng = np.random.default_rng(11)
    spec = [(5, 6, 0), (8, 5, 0), (4, 7, 1), (6, 4, 2), (5, 5, 4)]
    return [Request(i, [int(t) for t in rng.integers(0, 128, L)], n,
                    arrival=a)
            for i, (L, n, a) in enumerate(spec)]


def mk_engine(cfg, plan=None, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("budget", BUDGET)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_impl", "xla")
    return ServeEngine(cfg, params_for(cfg), fault_plan=plan, **kw)


def oracles(cfg, reqs):
    p = params_for(cfg)
    return {r.rid: lockstep_single(cfg, p, r.prompt, r.max_new_tokens,
                                   BUDGET)
            for r in reqs}


def assert_pool_drained(eng):
    """Every page back on the free list refcount-exact; prefix index
    empty (failure paths deregistered everything they published)."""
    for kind, alloc in eng.cache_mgr.alloc.items():
        assert alloc.n_held == 0, f"{kind}: {alloc.n_held} pages leaked"
        assert alloc.n_free == alloc.capacity, kind
    for idx in getattr(eng.cache_mgr, "prefix", {}).values():
        assert idx.state() == (), "prefix index retains registrations"


def assert_conformant(cfg, eng, reqs, expect_codes=None):
    """The chaos contract on a drained engine (see module doc)."""
    ref = oracles(cfg, reqs)
    for s in eng.sequences:
        assert s.status.terminal
        if s.status is Status.FINISHED:
            assert s.error is None
            assert s.out_tokens == ref[s.rid], \
                f"survivor rid={s.rid} diverged from the fault-free oracle"
        else:
            assert isinstance(s.error, ReproError)
            assert isinstance(s.error.code, Code)
            if expect_codes is not None:
                assert s.error.code in expect_codes, s.error
            assert s.out_tokens == ref[s.rid][:len(s.out_tokens)], \
                f"failed rid={s.rid} stream is not an oracle prefix"
    assert_pool_drained(eng)


# ------------------------------------------------ request validation -------

def test_request_validation_structured():
    with pytest.raises(ReproError) as e:
        Request(0, [], 4)
    assert e.value.code is Code.INVALID_VALUE
    with pytest.raises(ReproError) as e:
        Request(0, [1, 2], 0)
    assert e.value.code is Code.INVALID_VALUE
    with pytest.raises(ReproError) as e:
        Request(0, [1, 2], 4, deadline_ticks=-1)
    assert e.value.code is Code.INVALID_VALUE
    # and the engine-side budget check reports, not asserts
    eng = mk_engine(DENSE)
    with pytest.raises(ReproError) as e:
        eng.submit(Request(0, list(range(1, 30)), 8))
    assert e.value.code is Code.INVALID_VALUE


# ------------------------------------------- deadlines & cancellation ------

def test_deadline_exceeded_releases_and_survivors_stream():
    reqs = [Request(0, [1, 2, 3, 4, 5], 20, deadline_ticks=3),
            Request(1, [2, 3, 4], 5),
            Request(2, [3, 4, 5], 5)]
    eng = mk_engine(DENSE)
    eng.run(reqs)
    s0 = next(s for s in eng.sequences if s.rid == 0)
    assert s0.status is Status.FAILED
    assert s0.error.code is Code.DEADLINE_EXCEEDED
    assert 0 < len(s0.out_tokens) < 20      # streamed, then deadlined
    assert_conformant(DENSE, eng, reqs, {Code.DEADLINE_EXCEEDED})


def test_deadline_in_queue_never_binds_a_slot():
    # one slot, a long occupant, and a deadlined request stuck behind it
    reqs = [Request(0, [1, 2, 3, 4], 12),
            Request(1, [2, 3, 4, 5], 4, deadline_ticks=2)]
    eng = mk_engine(DENSE, n_slots=1)
    eng.run(reqs)
    s1 = next(s for s in eng.sequences if s.rid == 1)
    assert s1.status is Status.FAILED
    assert s1.error.code is Code.DEADLINE_EXCEEDED
    assert s1.out_tokens == [] and s1.slot == -1
    assert_conformant(DENSE, eng, reqs, {Code.DEADLINE_EXCEEDED})


def test_cancel_active_and_queued():
    reqs = [Request(i, [1 + i, 2, 3], 8) for i in range(4)]
    eng = mk_engine(DENSE, n_slots=2)
    seqs = [eng.submit(r) for r in reqs]
    eng.step()
    seqs[0].cancel()        # active
    seqs[3].cancel()        # still queued (2 slots)
    while not eng.done:
        eng.step()
    for i in (0, 3):
        assert seqs[i].status is Status.FAILED
        assert seqs[i].error.code is Code.CANCELLED
    assert seqs[3].out_tokens == []
    assert_conformant(DENSE, eng, reqs, {Code.CANCELLED})


def test_cancel_preempted_releases_swap():
    """Cancelling a sequence while it sits swapped-out in the wait queue
    must drop its swap blocks and leave the pool exact."""
    reqs = mk_trace()
    plan = FaultPlan(growth_oom={2})         # force one preemption
    eng = mk_engine(HYBRID, plan=plan)
    for r in reqs:
        eng.submit(r)
    cancelled = None
    for _ in range(200):
        eng.step()
        if cancelled is None:
            pre = [s for s in eng.sequences
                   if s.status is Status.PREEMPTED]
            if pre:
                pre[0].cancel()
                cancelled = pre[0]
        if eng.done:
            break
    eng.finish()
    assert cancelled is not None, "trace was meant to preempt"
    assert cancelled.status is Status.FAILED
    assert cancelled.error.code is Code.CANCELLED
    assert cancelled.swap is None
    assert_pool_drained(eng)


# ----------------------------------------------------- NaN quarantine ------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_nan_quarantine_isolates_slot(impl):
    cfg = dataclasses.replace(HYBRID, attn_impl=impl)
    reqs = mk_trace()
    plan = FaultPlan(nan_at={(0, 2)})
    eng = mk_engine(cfg, plan=plan)
    eng.run(reqs)
    failed = [s for s in eng.sequences if s.status is Status.FAILED]
    assert len(failed) == 1
    assert failed[0].error.code is Code.NUMERIC_FAULT
    # the poisoned token was never streamed
    assert_conformant(cfg, eng, reqs, {Code.NUMERIC_FAULT})


def test_nan_guard_off_streams_poison():
    """guards=False is the bench baseline: no quarantine, the argmax of
    a NaN row streams — proving the guard (not luck) provides isolation."""
    reqs = mk_trace()
    plan = FaultPlan(nan_at={(0, 2)})
    eng = mk_engine(DENSE, plan=plan, guards=False)
    eng.run(reqs)
    assert all(s.status is Status.FINISHED for s in eng.sequences)
    ref = oracles(DENSE, reqs)
    assert any(list(s.out_tokens) != ref[s.rid] for s in eng.sequences)


# ------------------------------------------------------- OOM failures ------

def test_injected_admission_oom_fails_only_that_request():
    reqs = mk_trace()
    plan = FaultPlan(admit_oom={2})
    eng = mk_engine(HYBRID, plan=plan)
    eng.run(reqs)
    s2 = next(s for s in eng.sequences if s.rid == 2)
    assert s2.status is Status.FAILED
    assert s2.error.code is Code.OUT_OF_RESOURCES
    assert s2.out_tokens == []
    assert_conformant(HYBRID, eng, reqs, {Code.OUT_OF_RESOURCES})


def test_growth_oom_single_active_fails_structured():
    """Pool exhaustion with nothing to preempt used to raise RuntimeError
    out of step(); now it fails that request and the engine lives on."""
    plan = FaultPlan(growth_oom={1})
    eng = mk_engine(DENSE, plan=plan, n_slots=1)
    seq = eng.submit(Request(0, [1, 2, 3, 4, 5], 8))
    nxt = eng.submit(Request(1, [2, 3, 4], 4, arrival=0))
    while not eng.done:
        eng.step()
    assert seq.status is Status.FAILED
    assert seq.error.code is Code.OUT_OF_RESOURCES
    # the engine kept serving: the next request completes normally
    assert nxt.status is Status.FINISHED
    assert_pool_drained(eng)


def test_growth_oom_absorbed_by_preemption():
    reqs = mk_trace()
    plan = FaultPlan(growth_oom={3})
    eng = mk_engine(HYBRID, plan=plan)
    streams = eng.run(reqs)
    assert eng.stats["preemptions"] >= 1
    assert streams == oracles(HYBRID, reqs)   # absorbed: bit-exact
    assert_pool_drained(eng)


# ------------------------------------------------------- lane faults -------

def test_transient_lane_fault_absorbed_by_retry():
    reqs = mk_trace()
    plan = FaultPlan(lane_faults=(
        LaneFault("Decode", "DECODE_KERNEL", 1, 2),
        LaneFault("Admit", "PREFILL_KERNEL", 0, 1)))
    eng = mk_engine(HYBRID, plan=plan, max_submission_retries=2)
    streams = eng.run(reqs)
    assert streams == oracles(HYBRID, reqs)
    assert eng.q_decode.retries == 2 and eng.q_admit.retries == 1
    assert all(s.status is Status.FINISHED for s in eng.sequences)
    assert_pool_drained(eng)


def test_persistent_admit_fault_fails_one_request():
    reqs = mk_trace()
    plan = FaultPlan(lane_faults=(
        LaneFault("Admit", "PREFILL_KERNEL", 1, 3),))
    eng = mk_engine(HYBRID, plan=plan, max_submission_retries=2)
    eng.run(reqs)
    failed = [s for s in eng.sequences if s.status is Status.FAILED]
    assert len(failed) == 1
    assert failed[0].error.code is Code.SUBMISSION_FAILURE
    # the injected fault is chained for post-mortem
    assert isinstance(failed[0].error.__cause__, InjectedFault)
    assert_conformant(HYBRID, eng, reqs, {Code.SUBMISSION_FAILURE})


def test_retry_without_policy_keeps_legacy_wrapping():
    """max_retries=0 keeps the pre-retry semantics: a foreign submission
    failure crosses the lane through guard()'s legacy foreign-exception
    wrap (INVALID_VALUE), never SUBMISSION_FAILURE — existing callers
    see unchanged classification and zero absorbed retries."""
    from repro.core import Context, DispatchQueue

    def boom():
        raise InjectedFault("flaky lane")

    q = DispatchQueue(Context.new_accel(), "lane")
    with pytest.raises(ReproError) as e:
        q.enqueue(boom)
    assert e.value.code is Code.INVALID_VALUE
    assert isinstance(e.value.__cause__, InjectedFault)
    assert q.retries == 0
    # and with a policy, the same failure is absorbed
    q2 = DispatchQueue(Context.new_accel(), "lane2", max_retries=2)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise InjectedFault("once")
        return 42

    assert q2.enqueue(flaky) == 42
    assert q2.retries == 1


def test_no_retry_policy_propagates_admission_lane_errors():
    """With max_submission_retries=0 a lane error at admission is not a
    per-request fault: it leaves step() with the original exception
    chained, instead of turning the request FAILED."""
    reqs = mk_trace()
    plan = FaultPlan(lane_faults=(
        LaneFault("Admit", "PREFILL_KERNEL", 0, 1),))
    eng = mk_engine(HYBRID, plan=plan, max_submission_retries=0)
    with pytest.raises(ReproError) as e:
        eng.run(reqs)
    assert e.value.code not in PER_REQUEST_CODES
    assert isinstance(e.value.__cause__, InjectedFault)
    assert not any(s.status is Status.FAILED for s in eng.sequences)


# ------------------------------------------------- chaos conformance -------

N_SEEDS = int(os.environ.get("CHAOS_SEEDS", "3"))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_chaos_conformance(impl, seed):
    """Seed sweep: any random FaultPlan leaves survivors byte-identical
    to the fault-free oracle and the pool refcount-exact (CHAOS_SEEDS
    env widens the sweep in the CI chaos lane)."""
    cfg = dataclasses.replace(HYBRID, attn_impl=impl)
    reqs = mk_trace()
    plan = FaultPlan.random(seed, n_slots=3, rids=[r.rid for r in reqs],
                            horizon=14, retries=2)
    eng = mk_engine(cfg, plan=plan, max_submission_retries=2)
    chaos_run(eng, reqs)
    assert_conformant(cfg, eng, reqs,
                      {Code.NUMERIC_FAULT, Code.OUT_OF_RESOURCES,
                       Code.SUBMISSION_FAILURE})


def test_chaos_outcomes_deterministic():
    """Same seed → identical per-request outcomes, streams, and codes."""
    reqs = mk_trace()
    outcomes = []
    for _ in range(2):
        plan = FaultPlan.random(7, n_slots=3,
                                rids=[r.rid for r in reqs],
                                horizon=14, retries=2)
        eng = mk_engine(HYBRID, plan=plan, max_submission_retries=2)
        streams = chaos_run(eng, reqs)
        outcomes.append((streams,
                         [(s.rid, s.status.value,
                           s.error.code.name if s.error else None)
                          for s in eng.sequences]))
    assert outcomes[0] == outcomes[1]


# --------------------------------------- supervisor + virtual clock --------

def test_chaos_run_drives_straggler_detection():
    """An injected slow-host stall lands a straggler event on the
    supervisor and the next healthy tick a recovery — all on virtual
    time, no sleeping, fully deterministic."""
    reqs = mk_trace()
    clock = VirtualClock()
    sup = Supervisor(1, dead_after_s=100.0, straggler_factor=2.0,
                     clock=clock.now)
    plan = FaultPlan(stalls={4: 0.5})       # 5× the 0.1s tick
    eng = mk_engine(HYBRID, plan=plan)
    streams = chaos_run(eng, reqs, clock=clock, supervisor=sup,
                        worker_id="serve-0", tick_s=0.1)
    kinds = [e[0] for e in sup.events]
    assert "straggler" in kinds and "recovered" in kinds
    assert kinds.index("straggler") < kinds.index("recovered")
    # the stall perturbed time, never data
    assert streams == oracles(HYBRID, reqs)


def test_fault_plan_rejects_unabsorbable_targets():
    with pytest.raises(AssertionError):
        FaultPlan(lane_faults=(LaneFault("Admit", "PAGE_SCRUB", 0, 1),))
    with pytest.raises(AssertionError):
        FaultPlan(lane_faults=(LaneFault("Decode", "SWAP_OUT", 0, 1),))
