"""Observability layer: request-level span traces, the typed metrics
registry behind ``engine.stats``, and the merged Perfetto export.

The load-bearing properties: (1) a request's lifecycle spans partition
its lifetime — contiguous, non-overlapping, one DECODE span per emitted
token — and TTFT falls out as an identity between the span trace and
the histogram; (2) every metric is recorded in engine ticks, so the
whole snapshot is equal across xla and pallas-interpret decode; (3)
``tracing=False`` changes nothing observable about the served streams;
(4) the exported trace_event JSON is schema-complete; (5) the engine's
host phases land in the profiler's trace as ``serve.*`` spans, nested
as DESIGN.md "Host phases on the profiler's clock" lists them."""

import dataclasses
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.core.errors import Code, err_string
from repro.ft.inject import FaultPlan
from repro.models import model as M
from repro.models.model import ModelConfig
from repro.prof import Prof
from repro.prof.export import (export_perfetto, perfetto_trace,
                               render_request_gantt)
from repro.prof.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                                StatsView)
from repro.prof.trace import RequestTrace, Span, SpanKind, TraceCollector
from repro.serve.engine import Request, ServeEngine

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "bench"))
from lib import enginespans  # noqa: E402

KEY = jax.random.PRNGKey(41)

TINY = dict(name="tiny-obs", family="dense", num_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
            dtype="float32")
DENSE = ModelConfig(**TINY)
# window < budget so decode wraps the swa ring back into shared pages —
# the only config whose steady-state decode triggers CoW (see
# test_prefix_sharing.py)
HYBRID = ModelConfig(**{**TINY, "name": "tiny-obs-hyb",
                        "pattern": (("swa", "dense"), ("full", "dense")),
                        "window": 16})

PARAMS = {}


def params_for(cfg):
    if cfg.name not in PARAMS:
        PARAMS[cfg.name] = M.init_params(cfg, KEY)
    return PARAMS[cfg.name]


def mk_trace(spec, seed=17):
    rng = np.random.default_rng(seed)
    return [Request(i, [int(t) for t in rng.integers(0, 128, L)], n,
                    arrival=a)
            for i, (L, n, a) in enumerate(spec)]


TRACE = [(5, 4, 0), (9, 7, 0), (3, 2, 1), (7, 5, 3), (4, 6, 4), (6, 3, 8)]


def run_dense(cfg=DENSE, tracing=True, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("budget", 24)
    eng = ServeEngine(cfg, params_for(cfg), tracing=tracing, **kw)
    streams = eng.run(mk_trace(TRACE))
    return eng, streams


# ------------------------------------------------ metrics primitives -------

class TestMetrics:
    def test_histogram_exact_below_64(self):
        h = Histogram("h")
        for v in [0, 1, 1, 2, 3, 5, 8, 13, 21, 34]:
            h.observe(v)
        # integer buckets 0..64: any percentile of small tick values is
        # exact, not a bucket upper bound
        assert h.percentile(50) == 3
        assert h.percentile(0) == 0
        assert h.percentile(100) == 34
        assert h.n == 10

    def test_histogram_tail_clamps_to_max(self):
        h = Histogram("h")
        h.observe(70)      # lands in a geometric tail bucket
        h.observe(100)
        p99 = h.percentile(99)
        assert p99 is not None and p99 <= 100, \
            "percentile must clamp to the observed max, not report the " \
            "bucket's upper bound"
        assert h.percentile(1) >= 65

    def test_histogram_empty(self):
        assert Histogram("h").percentile(99) is None

    def test_counter_gauge(self):
        c = Counter("c")
        c.inc()
        c.inc(3)
        assert c.value == 4
        g = Gauge("g")
        g.set(5)
        g.set(2)
        assert g.value == 2 and g.vmax == 5

    def test_registry_snapshot_and_render(self):
        r = MetricsRegistry()
        r.counter("hits")
        r.gauge("depth")
        r.histogram("lat_ticks")
        r.inc("hits", 2)
        r.set_gauge("depth", 7)
        for v in range(10):
            r.observe("lat_ticks", v)
        snap = r.snapshot()
        assert snap["hits"] == 2
        assert snap["depth"] == 7
        assert snap["lat_ticks"]["count"] == 10
        out = r.render()
        for name in ("hits", "depth", "lat_ticks"):
            assert name in out

    def test_registry_rejects_kind_collision(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(Exception):
            r.histogram("x")

    def test_stats_view_mapping(self):
        r = MetricsRegistry()
        r.counter("hits")
        r.inc("hits", 3)
        sv = StatsView(r, {"static": {"a": 1}, "dyn": lambda: 42})
        assert sv["hits"] == 3
        assert sv["static"] == {"a": 1}
        assert sv["dyn"] == 42            # callables are invoked on read
        assert set(iter(sv)) >= {"hits", "static", "dyn"}
        assert len(sv) == len(list(iter(sv)))
        with pytest.raises(KeyError):
            sv["nope"]


# ------------------------------------------------ span trace algebra -------

class TestTrace:
    def test_transitions_contiguous_by_construction(self):
        rt = RequestTrace(0, tick=0)
        rt.transition(SpanKind.PREFILL, 2)
        rt.transition(SpanKind.DECODE, 3, token_index=0)
        rt.mark(SpanKind.COW, 4, detail="1 pages")   # marker: no break
        rt.transition(SpanKind.DECODE, 5, token_index=1)
        rt.close(6)
        assert rt.contiguous()
        kinds = [s.kind for s in rt.lifecycle_spans()]
        assert kinds == [SpanKind.QUEUED, SpanKind.PREFILL,
                         SpanKind.DECODE, SpanKind.DECODE]
        assert [s.kind for s in rt.markers()] == [SpanKind.COW]

    def test_open_trace_not_contiguous(self):
        rt = RequestTrace(0, tick=0)
        rt.transition(SpanKind.PREFILL, 1)
        assert not rt.contiguous()        # PREFILL still open
        rt.close(2)
        assert rt.contiguous()

    def test_fail_closes_then_marks(self):
        rt = RequestTrace(0, tick=0)
        rt.fail(3, detail="boom")
        assert rt.contiguous()
        (m,) = rt.markers()
        assert m.kind is SpanKind.FAILED and m.detail == "boom"
        assert m.tick0 == m.tick1 == 3

    def test_marker_direction_asserts(self):
        rt = RequestTrace(0, tick=0)
        with pytest.raises(AssertionError):
            rt.transition(SpanKind.COW, 1)
        with pytest.raises(AssertionError):
            rt.mark(SpanKind.DECODE, 1)

    def test_link_after_close_is_noop(self):
        rt = RequestTrace(0, tick=0)
        rt.close(1)
        rt.link("late-event")             # release-path scrub: ignored
        assert all(not s.events for s in rt.spans)

    def test_collector_rejects_duplicate_rid(self):
        tc = TraceCollector()
        tc.begin(0, 0)
        with pytest.raises(AssertionError):
            tc.begin(0, 1)


# ------------------------------------------------ engine integration -------

class TestEngineSpans:
    def test_dense_spans_partition_and_ttft_identity(self):
        eng, streams = run_dense()
        assert eng.trace is not None and len(eng.trace) == len(TRACE)
        for rt in eng.trace:
            assert rt.contiguous(), rt.rid
            seq = next(s for s in eng.sequences if s.rid == rt.rid)
            decode = [s for s in rt.spans if s.kind is SpanKind.DECODE]
            # one DECODE span per emitted token, indices 0..n-1 in order
            assert [s.token_index for s in decode] == \
                list(range(len(streams[rt.rid])))
            # TTFT identity: histogram value == first DECODE start −
            # submission, measured purely from the span trace
            first = decode[0]
            assert first.tick0 - rt.spans[0].tick0 == \
                seq.admitted_at - seq.submitted_at
        # histogram agrees with the per-request identity
        ttfts = sorted(s.admitted_at - s.submitted_at
                       for s in eng.sequences)
        h = eng.metrics.histogram("ttft_ticks")
        assert h.n == len(TRACE)
        assert h.percentile(100) == ttfts[-1]

    def test_decode_spans_carry_kernel_events(self):
        eng, _ = run_dense()
        for rt in eng.trace:
            names = {e.name for s in rt.spans for e in s.events}
            assert "PREFILL_KERNEL" in names
            assert "DECODE_KERNEL" in names

    def test_tracing_off_streams_identical_and_cheap(self):
        eng_on, s_on = run_dense(tracing=True)
        eng_off, s_off = run_dense(tracing=False)
        assert s_on == s_off
        assert eng_off.trace is None
        # counters (the legacy stats surface) stay on either way
        assert eng_off.stats["decoded_tokens"] == \
            eng_on.stats["decoded_tokens"]
        # histograms are tracing-only
        assert eng_off.metrics.histogram("ttft_ticks").n == 0

    def test_preemption_emits_preempted_and_swap_spans(self):
        # force one preemption deterministically instead of relying on
        # pool pressure: growth OOM at tick 2 evicts the youngest
        plan = FaultPlan(growth_oom={2})
        eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=3,
                          budget=32, paged=True, page_size=4,
                          prefill_impl="xla", fault_plan=plan)
        rng = np.random.default_rng(11)
        reqs = [Request(i, [int(t) for t in rng.integers(0, 128, L)], n,
                        arrival=a)
                for i, (L, n, a) in enumerate(
                    [(5, 6, 0), (8, 5, 0), (4, 7, 1), (6, 4, 2)])]
        eng.run(reqs)
        assert eng.stats["preemptions"] >= 1
        kinds = {s.kind for rt in eng.trace for s in rt.spans}
        assert SpanKind.PREEMPTED in kinds and SpanKind.SWAP in kinds
        for rt in eng.trace:
            assert rt.contiguous(), rt.rid
            life = rt.lifecycle_spans()
            if any(s.kind is SpanKind.PREEMPTED for s in life):
                # the interrupted token's interval splits into two
                # DECODE spans with the same token_index around the
                # PREEMPTED→SWAP gap
                i = next(j for j, s in enumerate(life)
                         if s.kind is SpanKind.PREEMPTED)
                assert life[i - 1].kind is SpanKind.DECODE
                assert life[i + 1].kind is SpanKind.SWAP
                assert life[i + 2].kind is SpanKind.DECODE
                assert life[i + 2].token_index == life[i - 1].token_index

    def test_resume_observes_preempted_queue_wait(self):
        """A preempt → resume cycle is a real queue wait: the histogram
        must gain one observation per swap-in on top of one per
        admission (the old code reset ``admitted_at`` on resume without
        observing the wait, so preemption-heavy traces under-reported
        queue_wait_ticks)."""
        plan = FaultPlan(growth_oom={2})
        eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=3,
                          budget=32, paged=True, page_size=4,
                          prefill_impl="xla", fault_plan=plan)
        reqs = mk_trace([(5, 6, 0), (8, 5, 0), (4, 7, 1), (6, 4, 2)],
                        seed=11)
        eng.run(reqs)
        assert eng.stats["swap_ins"] >= 1
        h = eng.metrics.histogram("queue_wait_ticks")
        assert h.n == eng.stats["prefills"] + eng.stats["swap_ins"]
        # a resumed wait is at least one tick (preempted at t, back at
        # t+1 or later), so the histogram's tail reflects it
        assert h.percentile(100) >= 1

    def test_cow_markers_link_page_cow_events(self):
        # two sequences share a 2-page prefix; the swa ring wraps back
        # into the shared pages mid-decode → copy-on-write
        rng = np.random.default_rng(3)
        pre = [int(t) for t in rng.integers(0, 128, 8)]
        reqs = [Request(0, pre + [5, 9], 13, arrival=0),
                Request(1, pre + [7, 3], 13, arrival=0)]
        eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=2,
                          budget=24, paged=True, page_size=4,
                          prefill_impl="xla")
        eng.run(reqs)
        assert eng.stats["cow_copies"] >= 1
        cows = [s for rt in eng.trace for s in rt.markers()
                if s.kind is SpanKind.COW]
        assert cows, "cow_copies incremented but no COW marker emitted"
        assert sum(int(s.detail.split()[0]) for s in cows) == \
            eng.stats["cow_copies"]
        assert any(e.name == "PAGE_COW" for s in cows for e in s.events)

    def test_deadline_failure_marks_failed_with_err_string(self):
        rng = np.random.default_rng(17)
        spec = [(5, 12, None), (6, 12, None),
                (5, 12, 3), (7, 12, 3)]  # last two queue behind a full
        reqs = [Request(i,                # batch and deadline out
                        [int(t) for t in rng.integers(0, 128, L)], n,
                        arrival=0, deadline_ticks=d)
                for i, (L, n, d) in enumerate(spec)]
        eng = ServeEngine(DENSE, params_for(DENSE), n_slots=2, budget=24)
        eng.run(reqs)
        assert eng.stats["failures"] >= 1
        failed = [s for s in eng.sequences if s.error is not None]
        assert failed
        for seq in failed:
            rt = eng.trace.traces[seq.rid]
            assert rt.contiguous()
            (m,) = [s for s in rt.markers()
                    if s.kind is SpanKind.FAILED]
            assert m.detail == err_string(Code.DEADLINE_EXCEEDED)

    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_metrics_parity_xla_vs_pallas(self, paged):
        """Every histogram is in engine ticks, never wall time, so the
        full snapshot must be byte-comparable across decode backends."""
        snaps = {}
        for impl in ("xla", "pallas"):
            cfg = dataclasses.replace(DENSE, attn_impl=impl,
                                      name=f"tiny-obs-{impl}")
            PARAMS[cfg.name] = params_for(DENSE)
            kw = dict(paged=True, page_size=4,
                      prefill_impl="xla") if paged else {}
            eng = ServeEngine(cfg, params_for(DENSE), n_slots=3,
                              budget=24, **kw)
            eng.run(mk_trace(TRACE))
            snap = eng.metrics.snapshot()
            # compile counts are legitimately backend-specific (the
            # pallas path jits its own kernels) — everything else must
            # match exactly
            snap.pop("compiles_total")
            snaps[impl] = snap
        assert snaps["xla"] == snaps["pallas"]

    def test_fault_plan_replay_is_deterministic(self):
        """The injection log is part of the determinism contract: the
        same plan replayed against the same trace fires the same faults
        at the same coordinates."""
        logs = []
        for _ in range(2):
            plan = FaultPlan.random(7, n_slots=3, rids=[0, 1, 2, 3],
                                    horizon=20)
            eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=3,
                              budget=32, paged=True, page_size=4,
                              prefill_impl="xla", fault_plan=plan)
            eng.run(mk_trace([(5, 6, 0), (8, 5, 0), (4, 7, 1),
                              (6, 4, 2)], seed=11))
            logs.append(list(plan.fired))
        assert logs[0] == logs[1]


# ------------------------------------------------ export ------------------

# ------------------------------------------- host phases (profiler) -------

# each serve.* span and the span it runs inside
SERVE_PARENTS = {
    "serve.step": None,
    "serve.reap": "serve.step",
    "serve.admit": "serve.step",
    "serve.prefill": "serve.admit",
    "serve.align": "serve.admit",
    "serve.first_token": "serve.admit",
    "serve.swap_in": "serve.step",
    "serve.provision": "serve.step",
    "serve.table_sync": "serve.step",
    "serve.decode": "serve.step",
    "serve.logits_to_host": "serve.step",
    "serve.guard": "serve.step",
    "serve.sample": "serve.step",
    "serve.emit": "serve.step",
}


def _parent(span, spans):
    """Name of the shortest other span on ``span``'s thread that holds
    it, or None."""
    a, b = span["t0"], span["t0"] + span["dur"]
    holders = [s for s in spans if s is not span
               and (s["plane"], s["line"]) == (span["plane"], span["line"])
               and s["t0"] <= a and b <= s["t0"] + s["dur"]
               and s["dur"] >= span["dur"]]
    return min(holders, key=lambda s: s["dur"])["name"] if holders \
        else None


def test_host_phases_on_the_profiler_clock(tmp_path):
    """A paged engine with prefix sharing and one forced preemption,
    traced by the JAX profiler on the CPU: every ``serve.*`` span
    appears, under its parent, one ``serve.step`` per tick and one
    ``serve.admit`` per prefill."""
    rng = np.random.default_rng(5)
    pre = [int(t) for t in rng.integers(0, 128, 8)]
    reqs = [Request(i, pre + [int(t) for t in rng.integers(0, 128, k)],
                    n, arrival=a)
            for i, (k, n, a) in enumerate(
                [(2, 6, 0), (3, 5, 1), (1, 7, 1), (4, 4, 2)])]
    eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=3, budget=32,
                      paged=True, page_size=4, prefill_impl="xla",
                      fault_plan=FaultPlan(growth_oom={3}))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert eng.stats["shared_tokens"] > 0 and eng.stats["swap_ins"] >= 1
    spans = [e for e in enginespans.load(str(tmp_path))
             if e["name"].startswith("serve.")]
    names = [e["name"] for e in spans]
    assert set(names) == set(SERVE_PARENTS)
    for e in spans:
        assert _parent(e, spans) == SERVE_PARENTS[e["name"]], e
    assert names.count("serve.step") == eng.tick
    assert names.count("serve.admit") == eng.stats["prefills"]
    assert names.count("serve.swap_in") == eng.stats["swap_ins"]


class TestExport:
    def test_perfetto_schema_complete(self, tmp_path):
        eng, _ = run_dense()
        prof = Prof()
        prof.add_queue("Admit", eng.q_admit)
        prof.add_queue("Decode", eng.q_decode)
        prof.calc()
        path = tmp_path / "trace.json"
        export_perfetto(str(path), prof=prof, trace=eng.trace)
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert events
        for ev in events:
            assert {"ph", "ts", "pid", "tid"} <= set(ev)
        # both actors present: device lanes (pid 1) and requests (pid 2)
        assert {ev["pid"] for ev in events} >= {1, 2}
        # request tracks hold one complete event per lifecycle span
        n_req = sum(1 for ev in events
                    if ev["pid"] == 2 and ev["ph"] == "X")
        n_life = sum(len(rt.lifecycle_spans()) for rt in eng.trace)
        assert n_req == n_life
        # timestamps rebased: nothing starts before 0
        assert min(ev["ts"] for ev in events) >= 0

    def test_perfetto_markers_are_instants(self):
        rng = np.random.default_rng(3)
        pre = [int(t) for t in rng.integers(0, 128, 8)]
        reqs = [Request(0, pre + [5, 9], 13, arrival=0),
                Request(1, pre + [7, 3], 13, arrival=0)]
        eng = ServeEngine(HYBRID, params_for(HYBRID), n_slots=2,
                          budget=24, paged=True, page_size=4,
                          prefill_impl="xla")
        eng.run(reqs)
        doc = perfetto_trace(trace=eng.trace)
        instants = [ev for ev in doc["traceEvents"] if ev["ph"] == "i"]
        assert any(ev["name"].startswith("COW") for ev in instants)

    def test_gantt_renders_all_requests(self):
        eng, _ = run_dense()
        out = render_request_gantt(eng.trace, width=60)
        for rid in range(len(TRACE)):
            assert f"req {rid}" in out or f"{rid:2d}" in out
        # at least prefill and decode glyphs appear
        assert "P" in out and "#" in out
