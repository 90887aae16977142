"""Continuous-batching serve engine.

One :class:`ServeEngine` owns a standing batched KV cache of ``n_slots``
decode slots and runs a tick loop over it:

1. **admit** — queued requests are bound to free slots (FIFO); each
   admission prefills its prompt at batch=1, aligns the collected cache
   to the standing decode budget, and packs it into its slot of the
   batched cache (``cache_slot_insert``).  The first output token falls
   out of the prefill logits.
2. **decode** — one fused decode step advances *all* active slots
   together, each at its own depth: the engine hands
   ``model.decode_step`` the per-sequence ``(B,)`` position vector
   (``-1`` for idle slots, which are garbage-masked by construction).
3. **stream / retire** — each active slot's next token is streamed to
   its request; sequences that hit their budget or EOS release their
   slot, which the next tick's admission reuses.

Requests arrive, progress, and finish independently — sequences of
different prompt lengths and depths share every decode step, which is
what lockstep batching (``examples/serve_decode.py``) cannot do.

Device work is dispatched on two profiled ``DispatchQueue`` lanes
("Admit" carries ``PREFILL_KERNEL`` + ``ALIGN_CACHE`` + ``SLOT_INSERT``
submissions, "Decode" carries ``DECODE_KERNEL``), so ``prof.Prof`` shows
admission/prefill/decode interleaving with zero extra instrumentation —
the cf4ocl profiling model applied to serving.

**Paged mode** (``paged=True``): the standing cache is the paged KV pool
(``serve/paging.py``) instead of dense per-slot rings.  Admission binds
only the pages the prompt fills (the aligned prefill cache is cut into
page blocks and *donated* into the arenas — no slot-shaped copy exists),
decode grows each sequence one page at a time, and retirement returns
pages to the free list.  The scheduler gate becomes *pages free* rather
than slots free, and on pool exhaustion the engine **preempts the
sequence that frees the most pages** — the victim score is dominated by
*exclusive* pages reclaimed (``PagedCacheManager.exclusive_pages``;
evicting a fully-shared sequence frees almost nothing), tie-broken by
youngest arrival then rid for determinism.  The victim's page blocks
are swapped out verbatim, its exclusive pages freed (genuinely shared
prefix pages are *pinned* — kept resident and registered — so resume
re-attaches to them by reference), and it re-queues at the *front* of
the wait queue, so resumption restores the exact cache bits and the
output stream is bit-identical to an uninterrupted run.  Swapped blocks
stay device-resident (host offload is an open item) — preemption
relieves *pool* pressure, which is the contended resource.

**Prefix sharing** (``prefix_sharing=True``, paged mode only): identical
prompt prefixes cost one set of physical pages for the whole fleet.
Admission matches the prompt against the pool's
:class:`~repro.serve.paging.PrefixIndex`; the matched full-page run is
mapped by reference (refcount++), the prefill runs *partially* — from
the first unshared token, attending over the gathered shared prefix
(``PREFIX_GATHER`` + the same ``PREFILL_KERNEL`` event) — and the
donation scatter skips the shared span (those blocks sink into the null
page; the resident copies are already canonical).  Before any decode
write lands in a shared page (refcount > 1) the engine copies-on-write
(``PAGE_COW``): fresh page, jitted page copy, table-entry swap — so
streams stay bit-identical to unshared runs while resident pages and
prefill FLOPs drop with every shared prompt.

**Fault tolerance** (DESIGN.md "Failure model & graceful degradation"):
one bad request must never take down the batch.  Every per-sequence
fault lands in that sequence's error channel — a terminal ``FAILED``
status carrying a structured :class:`~repro.core.errors.ReproError` —
while the rest of the fleet streams on bit-identically:

* **deadlines / cancellation**: a request with ``deadline_ticks`` that
  has not finished within that many ticks of submission fails with
  ``DEADLINE_EXCEEDED``; a client calling ``Sequence.cancel()`` fails it
  with ``CANCELLED`` at the next tick.  Both work from any state —
  queued, active, or preempted.
* **admission OUT_OF_RESOURCES**: a prompt that needs more fresh pages
  than the arena could *ever* grant fails at admission instead of
  blocking the queue forever; transient pool pressure still just waits.
* **NaN/Inf quarantine**: a per-tick guard over the sampled logits fails
  only the poisoned slot (``NUMERIC_FAULT``) — the poisoned token is
  never emitted, so the failed stream is a clean prefix of its oracle.
* **lane retry**: dispatch-queue submissions are retried with bounded
  exponential backoff; exhaustion surfaces ``SUBMISSION_FAILURE``
  through the per-request error channel (admission-side faults fail that
  request only; a decode-lane exhaustion is batch-wide and fatal).  With
  no retry policy (``max_submission_retries=0``) a lane error — a
  compile or device failure — is not absorbed: it leaves ``step()``.

All failure paths release resources exactly: pages decref'd (shared
pages survive for their sharers), exclusive pages scrubbed and freed,
prefix-index registrations dropped, the slot returned.  ``guards=False``
disables the per-tick NaN check and deadline/cancel sweep — a
bench-only mode for measuring that the always-on guard path costs
effectively nothing (benchmark E11, the cf4ocl "negligible overhead"
claim reproduced for serving).  A deterministic
:class:`~repro.ft.inject.FaultPlan` can be attached to drive every one
of these paths from the chaos conformance suite.

**Shape buckets** (``buckets=True``, the default; DESIGN.md "Shape
discipline & bucketing"): every jitted step runs at a shape drawn from a
small static ladder, compiled once.  Per tick the active slots are
packed into the smallest covering power-of-two width bucket — slot rows,
tokens, positions and page tables gathered into dense ``(W,)`` tensors,
results scattered back — and each admission pads its prompt to a
page-aligned geometric length bucket with ``pos = -1`` masking, so a
trace with thousands of distinct prompt lengths and arrival patterns
compiles at most ``len(ladder)`` programs per step kind
(:class:`~repro.serve.step.BucketRegistry`; ``stats["compiles"]`` and
the ``TRACE_COMPILE`` events expose the counts).  Admission, the NaN
quarantine and fault injection all operate on *logical slots* — packed
indices never escape the decode tick.  Length bucketing changes the
floating-point reduction shapes of prefill, so one request's stream is
a function of its bucket, not its exact length; it is uniform across
arrival patterns (the conformance suite's oracle prefills at the same
bucket) and is disabled automatically for recurrent-state configs
(ssm / rec), whose prefill scan would fold padded steps into the state.
``buckets=False`` restores exact-shape prefill and always-full-width
decode (one retrace per distinct prompt length — the fixed-shape
baseline benchmark E12 prices against the ladder).

**Observability** (``tracing=True``, the default; DESIGN.md
"Observability"): every sequence carries a trace of typed lifecycle
spans (``prof.trace`` — QUEUED/PREFILL/DECODE-per-token/PREEMPTED/SWAP
plus COW/FAILED markers) emitted at the seams above, each linked to the
device :class:`~repro.core.event.Event` objects that served it, and a
:class:`~repro.prof.metrics.MetricsRegistry` records tick-based latency
histograms (TTFT, inter-token, queue wait, deadline margin, end-to-end)
and per-tick gauges.  ``engine.stats`` is a live
:class:`~repro.prof.metrics.StatsView` over the registry — the legacy
``stats["preemptions"]``-style reads keep working, and
``stats.percentile("ttft_ticks", 99)`` / ``stats.snapshot()`` expose
the SLO numbers benches report.  ``tracing=False`` skips span objects,
histogram observations and event linking (counters stay on — they are
the stats surface); streams are byte-identical either way.  Separately,
every phase of a tick is a ``serve.*`` host span on the JAX profiler's
clock (``prof.trace.host_span``; DESIGN.md "Host phases on the
profiler's clock"), so device idle time in a profiler trace is put down
to what the host was doing.  The spans are always emitted: with no
profiler running they cost about 5 µs a tick on a TPU v5e host (0.44
µs a span, 0.60 µs with a stat), 0.01 % of a 37–55 ms tick.

Simplifications (documented, not accidental): greedy sampling unless a
``sample_fn`` is supplied; one prefill per admission; the per-tick host
sync to read sampled tokens is the streaming boundary.  Cross-attention
(encoder/vision) models are not served — their context caches are
per-request and would need slot packing of ``ctx_enc`` too.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence as Seq

import jax.numpy as jnp
import numpy as np

from ...core import Context, DispatchQueue
from ...core.errors import Code, ReproError, err_string
from ...models import model as M
from ...prof.metrics import MetricsRegistry, StatsView
from ...prof.trace import SpanKind, TraceCollector, host_span
from .. import paging as P
from ..step import (ALIGN_EVENT, DECODE_EVENT, PREFILL_EVENT,
                    TRACE_AUTOTUNE_EVENT, BucketRegistry)
from ...core.event import Event
from ...kernels.autotune import ShapeKey, get_autotuner
from ...models.attention import KVCache
from .cache_manager import (BatchedCacheManager, CowBatch,
                            PagedCacheManager, insert_jit, paged_copy_jit,
                            paged_extract_jit, paged_gather_jit,
                            paged_insert_jit, paged_scrub_jit)
from .request import Request, Sequence, Status
from .scheduler import SlotScheduler

INSERT_EVENT = "SLOT_INSERT"
PAGE_INSERT_EVENT = "PAGE_INSERT"
SWAP_OUT_EVENT = "SWAP_OUT"
SWAP_IN_EVENT = "SWAP_IN"
SCRUB_EVENT = "PAGE_SCRUB"
PREFIX_GATHER_EVENT = "PREFIX_GATHER"
COW_EVENT = "PAGE_COW"

# -- the serve metric name registry (stable strings; see DESIGN.md
# "Observability" for the documented semantics of each) ------------------
# monotonic counters (unit: count) — always recorded, tracing on or off
COUNTER_METRICS = ("decode_steps", "decoded_tokens", "prefills",
                   "preemptions", "swap_ins", "prefill_tokens",
                   "shared_tokens", "prefix_hits", "cow_copies",
                   "resume_shared_tokens", "failures", "compiles_total",
                   "decode_pages_live", "decode_pages_budget")
# tick-based latency histograms (unit: engine ticks — deterministic,
# identical across numeric backends); recorded only while tracing
HISTOGRAM_METRICS = ("ttft_ticks", "tbt_ticks", "queue_wait_ticks",
                     "deadline_margin_ticks", "e2e_ticks")
# per-tick gauges (last value + high-water mark); recorded while tracing
GAUGE_METRICS = ("active_slots", "queue_depth", "pool_pages_held")

# admission faults that fail only the request they hit.  Any other error
# — a compile or device failure crossing a lane with no retry policy
# (``max_submission_retries=0``) — propagates out of ``step()``.
PER_REQUEST_CODES = frozenset({Code.SUBMISSION_FAILURE, Code.NUMERIC_FAULT,
                               Code.OUT_OF_RESOURCES})


class ServeEngine:
    def __init__(self, cfg: M.ModelConfig, params, *, n_slots: int = 4,
                 budget: int = 128, context: Optional[Context] = None,
                 prefill_impl: Optional[str] = None,
                 sample_fn: Optional[Callable[[np.ndarray], np.ndarray]]
                 = None, paged: bool = False, page_size: int = 4,
                 pool_pages: Optional[int] = None,
                 prefix_sharing: bool = True,
                 guards: bool = True,
                 buckets: bool = True,
                 fault_plan=None,
                 max_submission_retries: int = 2,
                 submission_backoff_s: float = 0.0,
                 tracing: bool = True,
                 autotune: bool = False):
        """``budget`` is the decode position budget: prompt length + new
        tokens of any request must fit in it.  ``prefill_impl`` overrides
        ``cfg.attn_impl`` for prefill only (e.g. decode on the fused
        Pallas kernel while prefill stays on XLA).  ``paged`` switches
        the standing cache to the paged KV pool; ``pool_pages`` caps the
        allocatable pages per cache kind (None = dense-equivalent full
        provision), which is where the memory win comes from.
        ``prefix_sharing`` (paged mode only) maps identical full-page
        prompt prefixes onto already-resident pages with copy-on-write.
        Partial (prefix-shared) prefill runs the same attention impl as
        one-shot prefill on every path — the flash kernel takes explicit
        position planes — so sharing stays enabled under Pallas prefill
        and shared/unshared prefills never mix kernels.

        ``autotune`` switches both prefill and decode to
        ``attn_impl="auto"``: every attention call resolves its shape
        key through the kernel autotuner (kernels/autotune.py — measured
        winners from the on-disk cache, deterministic cost model
        otherwise), and :meth:`warmup` resolves the ladder's shape keys
        eagerly, emitting one ``TRACE_AUTOTUNE`` event per key
        (``engine.autotune_events``).

        ``buckets`` (on by default) draws every jitted step shape from
        the static bucket ladders instead of exact shapes — see the
        module docstring; turn it off to reproduce the one-retrace-per-
        prompt-length baseline.

        ``guards`` enables the per-tick NaN/Inf quarantine and the
        deadline/cancellation sweep (on by default; benchmark E11 turns
        it off to price the guard path).  ``fault_plan`` attaches a
        deterministic :class:`~repro.ft.inject.FaultPlan` whose injected
        faults exercise every failure path.  Lane submissions are
        retried up to ``max_submission_retries`` times with exponential
        ``submission_backoff_s`` backoff before a structured
        ``SUBMISSION_FAILURE`` surfaces.  With 0 retries a lane error
        is not a per-request fault: it propagates out of :meth:`step`
        (``PER_REQUEST_CODES`` lists what admission absorbs).

        ``tracing`` (on by default) emits per-request lifecycle spans
        (``engine.trace``), links them to the device events that served
        them, and records the tick-based latency histograms/gauges;
        turning it off keeps only the counters (byte-identical streams
        either way).  The ``serve.*`` profiler spans do not depend on
        it."""
        assert not cfg.has_cross, \
            "serve engine does not support cross-attention models"
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.budget = budget
        self.paged = paged
        self.page_size = page_size
        if autotune:
            cfg = dataclasses.replace(cfg, attn_impl="auto")
            self.cfg = cfg
            prefill_impl = "auto"
        pcfg = cfg if prefill_impl is None else \
            dataclasses.replace(cfg, attn_impl=prefill_impl)
        self._pcfg = pcfg
        self.autotune = bool(autotune)
        self.autotune_events: List = []
        self.buckets = bool(buckets)
        self._registry = BucketRegistry(
            cfg, n_slots=n_slots, budget=budget,
            page_size=page_size if paged else None,
            prefill_cfg=pcfg, bucketing=self.buckets)
        # greedy by default; sample_fn maps (B, V) logits → (B,) tokens
        self._sample = sample_fn or (lambda lg: np.argmax(lg, axis=-1))

        self.scheduler = SlotScheduler(n_slots)
        if paged:
            self.cache_mgr = PagedCacheManager(cfg, n_slots, budget,
                                               page_size=page_size,
                                               pool_pages=pool_pages,
                                               prefix_sharing=prefix_sharing)
        else:
            self.cache_mgr = BatchedCacheManager(cfg, n_slots, budget)
        ctx = context or Context.new_accel()
        self.q_admit = DispatchQueue(ctx, "Admit",
                                     max_retries=max_submission_retries,
                                     backoff_s=submission_backoff_s)
        self.q_decode = DispatchQueue(ctx, "Decode",
                                      max_retries=max_submission_retries,
                                      backoff_s=submission_backoff_s)
        self.guards = guards
        self._plan = fault_plan
        if fault_plan is not None:
            fault_plan.reset()
            self.q_admit.fault_hook = \
                lambda ev, att: fault_plan.lane_fault("Admit", ev, att)
            self.q_decode.fault_hook = \
                lambda ev, att: fault_plan.lane_fault("Decode", ev, att)

        # host-side per-slot decode inputs (tick-batched to device)
        self._tokens = np.zeros((n_slots, 1), np.int32)
        self._pos = np.full((n_slots,), -1, np.int32)
        self._slot_seq: Dict[int, Sequence] = {}
        self.sequences: List[Sequence] = []
        # the *live* (non-terminal) subset, insertion-ordered — the only
        # sequences the per-tick reap and the done check walk, so per-
        # tick host work stays O(live), not O(total-ever-submitted), on
        # a long-running server (identity-keyed: Sequence is eq=False)
        self._live: Dict[int, Sequence] = {}
        self.tick = 0       # == ticks elapsed; steps/tokens in stats
        self.tracing = bool(tracing)
        self.metrics = MetricsRegistry()
        for name in COUNTER_METRICS:
            self.metrics.counter(name)
        for name in HISTOGRAM_METRICS:
            self.metrics.histogram(name, unit="ticks")
        for name in GAUGE_METRICS:
            self.metrics.gauge(name)
        self._registry.on_compile = \
            lambda kind: self.metrics.inc("compiles_total")
        self.trace = TraceCollector() if self.tracing else None
        self._n_compile_seen = 0    # TRACE_COMPILE link cursor
        # legacy stats surface: a live Mapping over the registry plus the
        # registry-owned compile dict and the lanes' absorbed retries
        self.stats = StatsView(self.metrics, {
            "compiles": self._registry.compiles,
            "lane_retries": lambda:
                self.q_admit.retries + self.q_decode.retries})

    @property
    def compile_events(self):
        """``TRACE_COMPILE`` profiler events recorded by the bucket
        registry (one per shape that actually compiled) — inject into a
        profiler with ``prof.add_events("Compile", eng.compile_events)``."""
        return self._registry.events

    def _link(self, seq: Sequence, queue: DispatchQueue) -> None:
        """Attach ``queue``'s most recent submission event to ``seq``'s
        open span — call right after the enqueue it belongs to (an
        enqueue may raise mid-admission, so never link speculatively)."""
        if self.trace is not None:
            ev = queue.last_event()
            if ev is not None:
                self.trace.link(seq.rid, ev)

    def _drain_compiles(self):
        """``TRACE_COMPILE`` events recorded since the last drain —
        warmup advances the cursor past its own compiles so pre-traffic
        compilation is never attributed to the first request."""
        evs = self._registry.events
        new = evs[self._n_compile_seen:]
        self._n_compile_seen = len(evs)
        return new

    def _warmup_autotune(self) -> None:
        """Resolve the ladder's attention shape keys through the
        autotuner before traffic: one ``TRACE_AUTOTUNE`` event per key,
        named with the key and the chosen config.  Host-side lookups
        only (measured cache / cost model) — sweeps run in the bench
        lane, never implicitly here."""
        if "auto" not in (self.cfg.attn_impl, self._pcfg.attn_impl):
            return
        tuner = get_autotuner()
        import jax as _jax
        backend = _jax.default_backend()
        Hq, D = self.cfg.n_heads, self.cfg.head_dim
        keys = []
        # decode keys come from the standing cache's actual KV layouts
        for leaf in _jax.tree.leaves(
                self.cache_mgr.cache,
                is_leaf=lambda x: isinstance(x, KVCache)):
            if not isinstance(leaf, KVCache):
                continue
            # arenas may carry leading layer/stack axes: read the
            # trailing (Hkv, span, D) regardless
            if leaf.page_table is not None:
                Hkv, ps = leaf.k.shape[-3], leaf.k.shape[-2]
                S = int(leaf.page_table.shape[-1]) * int(ps)
                keys.append(ShapeKey(
                    "decode_paged", cache_len=S, q_len=1, q_heads=Hq,
                    kv_heads=int(Hkv), head_dim=D, page_size=int(ps),
                    dtype=str(leaf.k.dtype), backend=backend))
            else:
                Hkv, S = leaf.k.shape[-3], leaf.k.shape[-2]
                keys.append(ShapeKey(
                    "decode", cache_len=int(S), q_len=1, q_heads=Hq,
                    kv_heads=int(Hkv), head_dim=D, page_size=0,
                    dtype=str(leaf.k.dtype), backend=backend))
        # one-shot prefill keys per length bucket (q_len == kv span)
        for Lb in self._registry.lengths:
            keys.append(ShapeKey(
                "flash", cache_len=int(Lb), q_len=int(Lb), q_heads=Hq,
                kv_heads=self.cfg.n_kv_heads, head_dim=D, page_size=0,
                dtype=self.cfg.dtype, backend=backend))
        for key in dict.fromkeys(keys):
            ev = Event("Autotune", TRACE_AUTOTUNE_EVENT,
                       name=f"{TRACE_AUTOTUNE_EVENT}:{key.encode()}")
            ev.mark_start()
            picked = tuner.choose(key)
            ev.mark_end()
            ev.name += f"→{picked.impl}" + (
                f"[bq={picked.block_q},bkv={picked.block_kv}]"
                if picked.impl == "pallas" else "")
            self.autotune_events.append(ev)

    def warmup(self) -> None:
        """Eagerly compile the bucket ladders (optional): every decode
        width, every prefill length bucket and its ring alignment, so a
        serving process takes the compile hits before traffic instead of
        on first use.  Outputs are discarded — the standing cache and all
        engine state are untouched.  Under ``autotune=True`` the ladder's
        shape keys are resolved first, so the compiles below bake the
        chosen configs in."""
        self._warmup_autotune()
        reg = self._registry
        cache = self.cache_mgr.cache
        for W in reg.widths:
            if W == self.n_slots:
                reg.decode_full()(self.params, cache,
                                  jnp.asarray(self._tokens),
                                  jnp.asarray(self._pos))
            else:
                pad = np.full((W,), self.n_slots, np.int32)
                reg.decode(W)(self.params, cache,
                              jnp.zeros((W, 1), jnp.int32),
                              jnp.full((W,), -1, jnp.int32),
                              jnp.asarray(pad))
        for Lb in reg.lengths:
            _, one = reg.prefill(Lb)(self.params,
                                     jnp.zeros((1, Lb), jnp.int32),
                                     jnp.int32(1))
            reg.align(Lb)(one, jnp.int32(1), jnp.int32(0))
        self._n_compile_seen = len(self._registry.events)

    # -- client side -----------------------------------------------------
    def submit(self, request: Request) -> Sequence:
        """Queue a request; tokens appear in ``sequence.out_tokens``."""
        if len(request.prompt) + request.max_new_tokens > self.budget:
            raise ReproError(
                Code.INVALID_VALUE,
                f"request {request.rid} exceeds the decode budget "
                f"{self.budget}")
        seq = self.scheduler.submit(request)
        seq.submitted_at = self.tick
        if self.trace is not None:
            self.trace.begin(seq.rid, self.tick)
        self.sequences.append(seq)
        self._live[id(seq)] = seq
        return seq

    @property
    def done(self) -> bool:
        return not self._live

    # -- lifecycle -------------------------------------------------------
    def _retire(self, seq: Sequence) -> None:
        seq.status = Status.FINISHED
        seq.finished_at = self.tick
        self._live.pop(id(seq), None)
        if self.tracing:
            e2e = self.tick - seq.submitted_at
            self.metrics.observe("e2e_ticks", e2e)
            if seq.request.deadline_ticks is not None:
                self.metrics.observe("deadline_margin_ticks",
                                     max(0, seq.request.deadline_ticks - e2e))
        self._release_slot(seq.slot)
        if self.trace is not None:
            self.trace.close(seq.rid, self.tick)

    def _release_slot(self, slot: int) -> None:
        self._pos[slot] = -1
        seq = self._slot_seq.pop(slot)
        if self.paged:
            # scrub the freed pages' validity planes before they return
            # to the free list (pool invariant: free pages carry pos=-1)
            ids = self.cache_mgr.release_slot(slot)
            cache = self.q_admit.enqueue(
                paged_scrub_jit, self.cfg, self.cache_mgr.cache, ids,
                name=SCRUB_EVENT, command_type=SCRUB_EVENT)
            self.cache_mgr.update(cache)
            self._link(seq, self.q_admit)
        self.scheduler.release(slot)

    def _fail(self, seq: Sequence, err: ReproError) -> None:
        """Terminate ``seq`` with a structured error, releasing whatever
        it holds: an active sequence gives back its slot (which decrefs
        shared pages, scrubs+frees exclusive ones, and drops its prefix
        registrations); a queued or preempted one is withdrawn from the
        wait queue.  The surviving batch is untouched."""
        if seq.slot >= 0 and self._slot_seq.get(seq.slot) is seq:
            self._release_slot(seq.slot)
        else:
            self.scheduler.remove(seq)
        if seq.kept_pages:
            # a preempted sequence dies holding prefix pins: drop them
            # (and scrub any page that reaches refcount 0) so failure
            # stays refcount-exact — co-sharers keep their pages
            self._drop_pins(seq)
        seq.swap = None
        seq.slot = -1
        seq.status = Status.FAILED
        seq.error = err
        seq.finished_at = self.tick
        self._live.pop(id(seq), None)
        self.metrics.inc("failures")
        if self.trace is not None:
            self.trace.fail(seq.rid, self.tick, detail=err_string(err.code))

    def _drop_pins(self, seq: Sequence) -> None:
        """Release a preempted sequence's pinned prefix pages (resume
        completed, the sequence died, or admission spilled the pins to
        relieve pool pressure), scrubbing any page that reached
        refcount 0 before it can be reused."""
        freed = self.cache_mgr.release_pinned(seq.kept_pages)
        seq.kept_pages = None
        seq.kept_tokens = 0
        if any((row != P.PAGE_NULL).any() for row in freed.values()):
            cache = self.q_admit.enqueue(
                paged_scrub_jit, self.cfg, self.cache_mgr.cache, freed,
                name=SCRUB_EVENT, command_type=SCRUB_EVENT)
            self.cache_mgr.update(cache)
            self._link(seq, self.q_admit)

    def _reap(self) -> List[Sequence]:
        """Deadline/cancellation sweep, run at the top of every tick:
        fail any non-terminal sequence whose client cancelled it or
        whose ``deadline_ticks`` budget has expired (cancellation wins
        when both apply the same tick).  Walks the live set only —
        per-tick cost is independent of how many sequences have ever
        been served."""
        failed = []
        for seq in list(self._live.values()):
            if seq.status.terminal:
                continue
            if seq.cancel_requested:
                self._fail(seq, ReproError(
                    Code.CANCELLED,
                    f"request {seq.rid} cancelled by client at tick "
                    f"{self.tick}"))
            elif (seq.request.deadline_ticks is not None and
                  self.tick - seq.submitted_at >=
                  seq.request.deadline_ticks):
                self._fail(seq, ReproError(
                    Code.DEADLINE_EXCEEDED,
                    f"request {seq.rid} missed its deadline of "
                    f"{seq.request.deadline_ticks} ticks "
                    f"(submitted at tick {seq.submitted_at})"))
            else:
                continue
            failed.append(seq)
        return failed

    def _bind(self, seq: Sequence, slot: int, first_tok: int) -> None:
        """Common post-admission bookkeeping: activate, stream the first
        token (which may retire a one-token request on the spot), arm the
        slot's decode inputs."""
        seq.status = Status.ACTIVE
        seq.admitted_at = self.tick
        seq.last_emit_tick = self.tick
        self._slot_seq[slot] = seq
        if self.tracing:
            # TTFT: token 0 falls out of the prefill logits, so first
            # token time == queue wait + (zero-tick) admission
            wait = self.tick - seq.submitted_at
            self.metrics.observe("queue_wait_ticks", wait)
            self.metrics.observe("ttft_ticks", wait)
        if self.trace is not None:
            self.trace.transition(seq.rid, SpanKind.DECODE, self.tick,
                                  token_index=0)
        if seq.emit(first_tok):
            self._retire(seq)
        else:
            self._tokens[slot, 0] = first_tok
            self._pos[slot] = seq.pos

    def _prefill_admit(self, seq: Sequence, slot: int,
                       shared_toks: int = 0,
                       shared_ids: Optional[Dict] = None) -> None:
        tokens = seq.request.prompt
        reg = self._registry
        L = seq.prompt_len
        if self.trace is not None:
            self.trace.transition(seq.rid, SpanKind.PREFILL, self.tick)
        with host_span("serve.prefill",
                       bucket=reg.len_bucket(L - shared_toks)):
            if shared_toks:
                # prefix sharing: gather the resident shared span back into
                # prefill layout and prefill only the unshared tail — both
                # on the Admit lane, so the gather orders after the donor's
                # own page inserts and the partial prefill after the gather.
                # The page-id run is padded to its power-of-two bucket with
                # null pages (pos = -1, masked) so the gather and the
                # partial prefill compile once per bucket pair, not once per
                # (prefix, tail) length pair.
                seq.shared_tokens = shared_toks
                self.metrics.inc("prefix_hits")
                self.metrics.inc("shared_tokens", shared_toks)
                m = shared_toks // self.page_size
                m_b = reg.page_bucket(m)
                pad_ids = {}
                for k, v in shared_ids.items():
                    row = np.full(m_b, P.PAGE_NULL, np.int32)
                    row[:m] = v
                    pad_ids[k] = jnp.asarray(row)
                prefix = self.q_admit.enqueue(
                    paged_gather_jit, self.cfg, self.cache_mgr.cache, pad_ids,
                    name=PREFIX_GATHER_EVENT, command_type=PREFIX_GATHER_EVENT)
                self._link(seq, self.q_admit)
                prefix_pad = m_b * self.page_size
                tail_len = reg.len_bucket(L - shared_toks)
                tail = np.zeros((1, tail_len), np.int32)
                tail[0, :L - shared_toks] = tokens[shared_toks:]
                logits, cache = self.q_admit.enqueue(
                    reg.prefill_ext(prefix_pad, tail_len), self.params,
                    jnp.asarray(tail), prefix, jnp.int32(shared_toks),
                    jnp.int32(L),
                    name=PREFILL_EVENT, command_type=PREFILL_EVENT)
                self._link(seq, self.q_admit)
                ring_len = prefix_pad + tail_len
            else:
                ring_len = reg.len_bucket(L)
                prefix_pad = 0
                prompt = np.zeros((1, ring_len), np.int32)
                prompt[0, :L] = tokens
                logits, cache = self.q_admit.enqueue(
                    reg.prefill(ring_len), self.params, jnp.asarray(prompt),
                    jnp.int32(L),
                    name=PREFILL_EVENT, command_type=PREFILL_EVENT)
                self._link(seq, self.q_admit)
        self.metrics.inc("prefill_tokens", seq.prompt_len - shared_toks)
        with host_span("serve.align"):
            # relayout and slot packing are enqueued as *pure* jitted fns
            # whose outputs are the events' outputs — finish() fences
            # them and the spans track the copies, not host dispatch
            align = reg.align(ring_len, prefix_pad)
            if self.paged:
                blocks = self.q_admit.enqueue(
                    align, cache, jnp.int32(L), jnp.int32(shared_toks),
                    name=ALIGN_EVENT, command_type=ALIGN_EVENT)
                self._link(seq, self.q_admit)
                ids = self.cache_mgr.table_ids(slot)
                if shared_toks:
                    # donation skips the shared span: its blocks sink into
                    # the null page — the resident copies are already
                    # canonical and a scatter into them would be a write to
                    # refcount>1 pages
                    for kind in ids:
                        ids[kind][:shared_toks // self.page_size] = P.PAGE_NULL
                packed = self.q_admit.enqueue(
                    paged_insert_jit, self.cfg, self.cache_mgr.cache, blocks,
                    ids, jnp.int32(slot),
                    name=PAGE_INSERT_EVENT, command_type=PAGE_INSERT_EVENT)
                self._link(seq, self.q_admit)
            else:
                cache = self.q_admit.enqueue(
                    align, cache, jnp.int32(L), jnp.int32(0),
                    name=ALIGN_EVENT, command_type=ALIGN_EVENT)
                self._link(seq, self.q_admit)
                packed = self.q_admit.enqueue(
                    insert_jit, self.cache_mgr.cache, cache, jnp.int32(slot),
                    name=INSERT_EVENT, command_type=INSERT_EVENT)
                self._link(seq, self.q_admit)
            self.cache_mgr.update(packed)
            if self.paged:
                # publish this prompt's full-page blocks for later arrivals
                # (host-side; the content lands via Admit-lane ordering);
                # the sequence's chain reuses the admission-time hashes
                self.cache_mgr.register_prefix(slot, tokens,
                                               chain=seq.prefix_chain)
        self.metrics.inc("prefills")
        if self.trace is not None:
            # any bucket that compiled during this admission served it
            self.trace.link(seq.rid, *self._drain_compiles())
        seq.pos = seq.prompt_len
        with host_span("serve.first_token"):
            # first output token comes from the prefill logits
            lg = np.asarray(logits[:, -1])
            if self.guards and not np.isfinite(lg).all():
                raise ReproError(
                    Code.NUMERIC_FAULT,
                    f"request {seq.rid}: non-finite prefill logits")
            t0 = int(self._sample(lg)[0])
        self._bind(seq, slot, t0)

    def _swap_in(self, seq: Sequence, slot: int,
                 shared_toks: int = 0) -> None:
        """Resume a preempted sequence: scatter its swapped page blocks
        into freshly bound pages and restore its decode inputs verbatim
        (bit-identical to never having been preempted).

        ``shared_toks`` is the re-matched prefix (``match_resume``): the
        first ``shared_toks // page_size`` table entries were mapped by
        reference by ``admit_pages``, so the restore scatter *skips*
        them (their blob blocks sink into the null page — the resident
        copies are already canonical, and a scatter into them would be a
        write to refcount>1 pages).  Only the exclusive remainder is
        restored from the blob — a preempt → resume cycle no longer
        duplicates shared prefix pages into private copies."""
        if self.trace is not None:
            self.trace.transition(seq.rid, SpanKind.SWAP, self.tick)
        ids = self.cache_mgr.table_ids(slot)
        if shared_toks:
            m = shared_toks // self.page_size
            for kind in ids:
                ids[kind][:m] = P.PAGE_NULL
            self.metrics.inc("resume_shared_tokens", shared_toks)
        packed = self.q_admit.enqueue(
            paged_insert_jit, self.cfg, self.cache_mgr.cache, seq.swap,
            ids, jnp.int32(slot),
            name=SWAP_IN_EVENT, command_type=SWAP_IN_EVENT)
        self._link(seq, self.q_admit)
        self.cache_mgr.update(packed)
        seq.swap = None
        self.metrics.inc("swap_ins")
        seq.status = Status.ACTIVE
        if self.tracing:
            # the preempted wait is a real queue wait: without this the
            # queue_wait_ticks histogram under-reports preemption-heavy
            # traces (the first wait was observed at first admission)
            self.metrics.observe("queue_wait_ticks",
                                 self.tick - seq.preempted_at)
        seq.admitted_at = self.tick
        self._slot_seq[slot] = seq
        if seq.kept_pages:
            # admission re-shared the still-matched pages (refcount++),
            # so the preemption-time pins are now redundant — drop them
            self._drop_pins(seq)
        if self.trace is not None:
            # resume the interrupted token's service interval: same
            # token_index as the span the preemption cut short
            self.trace.transition(seq.rid, SpanKind.DECODE, self.tick,
                                  token_index=len(seq.out_tokens) - 1)
        self._tokens[slot, 0] = seq.next_tok
        self._pos[slot] = seq.pos

    def _admit_fail(self, seq: Sequence, slot: int,
                    err: ReproError) -> None:
        """A fault mid-admission (prefill / align / insert): make the
        half-admitted sequence look active on its slot, then fail it —
        ``_fail``'s release path returns the slot and every page the
        admission bound (shared pages decref'd, fresh ones scrubbed and
        freed, prefix registrations dropped)."""
        self._slot_seq[slot] = seq
        seq.status = Status.ACTIVE
        self._fail(seq, err)

    def _admit(self) -> List[Sequence]:
        if not self.paged:
            admitted = []
            for seq, slot in self.scheduler.admit():
                with host_span("serve.admit", rid=seq.rid,
                               prompt_len=seq.prompt_len,
                               shared_tokens=0) as span:
                    try:
                        self._prefill_admit(seq, slot)
                    except ReproError as e:
                        if e.code not in PER_REQUEST_CODES:
                            raise
                        self._admit_fail(seq, slot, e)
                    self._note_queue_wait(span, seq)
                admitted.append(seq)
            return admitted
        # paged: gate each admission on pages free, not just slots free.
        # Gating the head blocks the queue — FIFO admission stays FIFO.
        admitted = []
        while True:
            head = self.scheduler.peek()
            if head is None:
                break
            resume = head.status is Status.PREEMPTED
            if resume:
                # re-match the resumed sequence's *written* token run
                # against the prefix index: still-resident prefix pages
                # (including everything the preemption pinned) are
                # mapped by reference and only the exclusive remainder
                # is restored from the swap blob
                if head.prefix_chain is None:
                    head.prefix_chain = P.PrefixChain(self.page_size)
                shared_toks, shared_ids = self.cache_mgr.match_resume(
                    head.written_tokens, chain=head.prefix_chain)
                need = head.pos
            else:
                if head.prefix_chain is None:
                    head.prefix_chain = P.PrefixChain(self.page_size)
                shared_toks, shared_ids = self.cache_mgr.match_prefix(
                    head.request.prompt, chain=head.prefix_chain)
                need = head.prompt_len
            shared_pages = shared_toks // self.page_size
            # a prompt the arena could never hold fails *now* (structured
            # OUT_OF_RESOURCES) instead of blocking the queue forever;
            # transient pool pressure falls through to the wait gate
            if not resume and (
                    (self._plan is not None and
                     self._plan.admission_oom(head.rid)) or
                    not self.cache_mgr.can_ever_admit(
                        need, shared_pages=shared_pages)):
                self._fail(head, ReproError(
                    Code.OUT_OF_RESOURCES,
                    f"request {head.rid}: prompt needs more fresh pages "
                    f"than the pool can ever grant"))
                admitted.append(head)
                continue
            # the gate counts shared pages once: only the fresh
            # remainder must be free
            if not self.cache_mgr.can_admit(need,
                                            shared_pages=shared_pages):
                # with no active sequence to preempt, the only pages the
                # pool can still give back are prefix pins held by other
                # preempted sequences — spill the youngest pinner's pins
                # (it resumes last) and re-evaluate, so pinning can
                # never wedge admission the pre-pin engine would have
                # served
                if not self._slot_seq and self._spill_one_pin(head):
                    continue
                break
            # a resumption is no admission: it restores a swapped-out
            # sequence, so it gets a span of its own
            span = host_span("serve.swap_in", rid=head.rid) if resume \
                else host_span("serve.admit", rid=head.rid,
                               prompt_len=head.prompt_len,
                               shared_tokens=shared_toks)
            with span:
                seq, slot = self.scheduler.pop_bind()
                ok = self.cache_mgr.admit_pages(slot, need,
                                                shared=shared_ids)
                assert ok, "gate passed but allocation failed"
                try:
                    if resume:
                        self._swap_in(seq, slot, shared_toks)
                    else:
                        self._prefill_admit(seq, slot, shared_toks,
                                            shared_ids)
                except ReproError as e:
                    if e.code not in PER_REQUEST_CODES:
                        raise
                    self._admit_fail(seq, slot, e)
                if not resume:
                    self._note_queue_wait(span, seq)
            admitted.append(seq)
        return admitted

    def _note_queue_wait(self, span, seq: Sequence) -> None:
        """Record on an admission's host span how long ``seq`` sat
        queued, in µs: the duration of its ``QUEUED`` span, so the
        request trace (``perf_counter`` clock) joins the profiler's trace
        by ``rid`` and this number."""
        if self.trace is not None:
            q = self.trace.traces[seq.rid].spans[0]
            if q.t1 is not None:
                span.set_metadata(queue_wait_us=(q.t1 - q.t0) // 1000)

    def _spill_one_pin(self, head: Sequence) -> bool:
        """Release one preempted sequence's pinned prefix pages to
        relieve pool pressure when admission is gated with no active
        victim left.  Spills youngest (latest arrival, ties by rid)
        first so ``head`` — the next to resume — keeps its pins longest;
        True iff a pin set was spilled (the caller re-gates)."""
        pinners = [s for s in self._live.values()
                   if s.status is Status.PREEMPTED and s.kept_pages]
        if not pinners:
            return False
        victim = max(pinners, key=lambda s: (s is not head,
                                             s.request.arrival, s.rid))
        self._drop_pins(victim)
        return True

    # -- paged-pool pressure ---------------------------------------------
    def _preempt_one(self) -> Sequence:
        """Evict the active sequence whose eviction frees the most pool
        pages: the victim score is dominated by *exclusive* pages
        reclaimed (``exclusive_pages`` — a fully-shared sequence frees
        ~0 pages and is never chosen over one holding private pages),
        tie-broken by youngest arrival then rid for determinism (which
        is exactly the old policy whenever scores tie, e.g. with sharing
        off).  The victim's genuinely shared prefix pages are *pinned*
        before its row references drop — they stay resident and
        registered so resumption re-attaches by reference — then its
        page blocks are swapped out, its exclusive pages freed, and it
        requeues at the front.  Returns the victim."""
        cands = list(self._slot_seq.values())
        if len(cands) <= 1:
            raise RuntimeError(
                "paged pool exhausted with a single active sequence — "
                "the arena cannot hold one budget-length request")
        mgr = self.cache_mgr
        victim = max(cands, key=lambda s: (mgr.exclusive_pages(s.slot),
                                           s.request.arrival, s.rid))
        slot = victim.slot
        if self.trace is not None:
            # transition first so the swap-out + scrub events land on
            # the PREEMPTED span, not the interrupted DECODE span
            self.trace.transition(victim.rid, SpanKind.PREEMPTED,
                                  self.tick)
        victim.kept_tokens, victim.kept_pages = mgr.pin_shared_prefix(
            slot, victim.written_tokens, chain=victim.prefix_chain)
        # the blob is the full row — blocks for pinned pages are
        # redundant (registered pages are immutable, so the blob copy
        # equals the live bits) but keep the extract shape uniform and
        # make pin-spilling safe: a spilled resume restores everything
        victim.swap = self.q_admit.enqueue(
            paged_extract_jit, self.cfg, self.cache_mgr.cache,
            self.cache_mgr.table_ids(slot), jnp.int32(slot),
            name=SWAP_OUT_EVENT, command_type=SWAP_OUT_EVENT)
        self._link(victim, self.q_admit)
        victim.next_tok = int(self._tokens[slot, 0])
        victim.status = Status.PREEMPTED
        victim.preempted_at = self.tick
        victim.preemptions += 1
        victim.slot = -1
        self._release_slot(slot)
        self.scheduler.requeue_front(victim)
        self.metrics.inc("preemptions")
        return victim

    def _provision(self) -> List[Sequence]:
        """Back every active slot's next ring write with a *writable*
        page: lazy growth, copy-on-write off shared pages (refcount >
        1), preempting the youngest sequence(s) on pool exhaustion.
        All CoW copies of a tick are coalesced into **one** jitted
        gather-copy on the Decode lane ahead of the decode step, so the
        writes always land in the private copies without paying one
        dispatch per slot; the copy lists are padded to a power-of-two
        width with null→null identity copies so the copy program
        compiles once per width bucket.  Pending copies are flushed
        before any preemption or failure — their extract/scrub must
        observe the copied-into pages.  Exhaustion with a single active
        sequence cannot be relieved by preemption — that sequence fails
        with OUT_OF_RESOURCES (returned here) and the engine keeps
        serving."""
        failed: List[Sequence] = []
        batch = CowBatch(self.cache_mgr.widths)
        contrib: List = []      # (seq, n_copies) charged this batch

        def flush() -> None:
            pending = batch.drain()
            if pending is None:
                contrib.clear()
                return
            src, dst = pending
            cache = self.q_decode.enqueue(
                paged_copy_jit, self.cfg, self.cache_mgr.cache,
                src, dst, name=COW_EVENT, command_type=COW_EVENT)
            self.cache_mgr.update(cache)
            if self.trace is not None:
                ev = self.q_decode.last_event()
                for s, n in contrib:
                    self.trace.mark(
                        s.rid, SpanKind.COW, self.tick,
                        detail=f"{n} pages",
                        events=(ev,) if ev is not None else ())
            contrib.clear()

        for slot in sorted(self._slot_seq):
            while slot in self._slot_seq:
                forced = (self._plan is not None and
                          self._plan.take_growth_oom(self.tick))
                plan = None if forced else self.cache_mgr.prepare_write(
                    slot, int(self._pos[slot]))
                if plan is None:
                    # the victim's swap-out / scrub must read pages the
                    # pending copies have already written
                    flush()
                    if len(self._slot_seq) <= 1:
                        # no victim to evict: the arena cannot back this
                        # sequence's next write even alone — fail it
                        # instead of deadlocking the pool
                        seq = self._slot_seq[slot]
                        self._fail(seq, ReproError(
                            Code.OUT_OF_RESOURCES,
                            f"request {seq.rid}: paged pool exhausted "
                            f"with a single active sequence"))
                        failed.append(seq)
                        break
                    # pool dry: evict and re-plan (the eviction may have
                    # dropped a refcount to 1, obviating a copy)
                    self._preempt_one()
                    continue
                n_cow = batch.add(plan)
                self.metrics.inc("cow_copies", n_cow)
                if n_cow and self.trace is not None:
                    contrib.append((self._slot_seq[slot], n_cow))
                break
        flush()
        return failed

    def _decode_tick(self) -> List[Sequence]:
        finished: List[Sequence] = []
        if self.paged:
            with host_span("serve.provision") as span:
                cow0 = self.stats["cow_copies"]
                finished += self._provision()
                span.set_metadata(cow_pages=self.stats["cow_copies"] - cow0)
            with host_span("serve.table_sync"):
                self.cache_mgr.sync()
        active = sorted(self._slot_seq)
        if not active:
            return finished
        width = self._registry.width_bucket(len(active))
        na = len(active)
        with host_span("serve.decode", width=width, active=na):
            if width < self.n_slots:
                # pack the active slots into the smallest covering width
                # bucket: dense (W,) tokens/positions/rows in, per-slot
                # results scattered back inside the jitted step.  Padding
                # rows carry the out-of-bounds sentinel n_slots and behave
                # exactly like idle slots of the full-width path.
                rows = np.full((width,), self.n_slots, np.int32)
                rows[:na] = active
                tok = np.zeros((width, 1), np.int32)
                tok[:na] = self._tokens[active]
                pos = np.full((width,), -1, np.int32)
                pos[:na] = self._pos[active]
                logits, cache = self.q_decode.enqueue(
                    self._registry.decode(width), self.params,
                    self.cache_mgr.cache, jnp.asarray(tok),
                    jnp.asarray(pos), jnp.asarray(rows),
                    name=DECODE_EVENT, command_type=DECODE_EVENT)
            else:
                logits, cache = self.q_decode.enqueue(
                    self._registry.decode_full(), self.params,
                    self.cache_mgr.cache,
                    jnp.asarray(self._tokens), jnp.asarray(self._pos),
                    name=DECODE_EVENT, command_type=DECODE_EVENT)
            self.cache_mgr.update(cache)
            self.metrics.inc("decode_steps")
            if self.paged:
                live, budget = self.cache_mgr.decode_walk(
                    pos if width < self.n_slots else self._pos)
                self.metrics.inc("decode_pages_live", live)
                self.metrics.inc("decode_pages_budget", budget)
        with host_span("serve.logits_to_host"):
            if width < self.n_slots:
                packed_lg = np.asarray(logits[:, 0])          # (W, V)
                # expand to slot-indexed logits so sampling, fault
                # injection and the quarantine stay on logical slots
                lg = np.zeros((self.n_slots,) + packed_lg.shape[1:],
                              packed_lg.dtype)
                lg[active] = packed_lg[:na]
            else:
                lg = np.asarray(logits[:, 0])             # (n_slots, V)
        decode_ev = None
        if self.trace is not None:
            decode_ev = self.q_decode.last_event()
            compiles = self._drain_compiles()
            if compiles:
                # a decode-width compile this tick served every packed slot
                for slot in active:
                    self.trace.link(self._slot_seq[slot].rid, *compiles)
        if self._plan is not None:
            lg = self._plan.corrupt_logits(lg, self.tick)
        if self.guards:
            with host_span("serve.guard"):
                # NaN/Inf quarantine: fail only the poisoned slots,
                # *before* sampling streams a garbage token — the failed
                # stream stays a clean prefix of its fault-free oracle and
                # every other slot decodes on unperturbed
                for slot in list(active):
                    if not np.isfinite(lg[slot]).all():
                        seq = self._slot_seq[slot]
                        self._fail(seq, ReproError(
                            Code.NUMERIC_FAULT,
                            f"request {seq.rid}: non-finite decode logits "
                            f"at tick {self.tick} (slot {slot})"))
                        finished.append(seq)
                        active.remove(slot)
        with host_span("serve.sample"):
            nxt = self._sample(lg)                        # (n_slots,)
        with host_span("serve.emit"):
            for slot in active:
                seq = self._slot_seq[slot]
                tok = int(nxt[slot])
                seq.pos += 1
                self.metrics.inc("decoded_tokens")
                if self.tracing:
                    self.metrics.observe("tbt_ticks",
                                         self.tick - seq.last_emit_tick)
                seq.last_emit_tick = self.tick
                if decode_ev is not None:
                    # link before the transition: the kernel served the
                    # span that was open while this token was in flight
                    self.trace.link(seq.rid, decode_ev)
                done = seq.emit(tok)
                if self.trace is not None:
                    self.trace.transition(
                        seq.rid, SpanKind.DECODE, self.tick,
                        token_index=len(seq.out_tokens) - 1)
                if done:
                    self._retire(seq)
                    finished.append(seq)
                else:
                    if self.paged and self.cache_mgr.sharing and \
                            seq.pos % self.page_size == 0:
                        # a full page of decode-produced tokens just
                        # closed: publish it so later prompts extending
                        # this sequence's prompt + output share past the
                        # prompt (agentic fan-out; CoW handles divergence)
                        self.cache_mgr.register_decode_page(
                            slot, seq.written_tokens,
                            chain=seq.prefix_chain)
                    self._tokens[slot, 0] = tok
                    self._pos[slot] = seq.pos
        return finished

    def step(self) -> List[Sequence]:
        """One engine tick: reap deadlines/cancellations, admit, then
        one batched decode step.

        Returns the sequences that reached a *terminal* state this tick
        — FINISHED or FAILED; callers distinguish via ``status`` and
        read the structured error from ``Sequence.error``.

        Each phase of the tick is a ``serve.*`` host span on the
        profiler's clock (DESIGN.md "Host phases on the profiler's
        clock")."""
        with host_span("serve.step", tick=self.tick):
            finished = []
            if self.guards:
                with host_span("serve.reap"):
                    finished += self._reap()
            finished += [s for s in self._admit() if s.status.terminal]
            finished += self._decode_tick()
            if self.tracing:
                self.metrics.set_gauge("active_slots", len(self._slot_seq))
                self.metrics.set_gauge("queue_depth",
                                       self.scheduler.n_waiting)
                if self.paged:
                    self.metrics.set_gauge(
                        "pool_pages_held",
                        sum(self.cache_mgr.pages_held().values()))
            self.tick += 1
        return finished

    def run(self, requests: Seq[Request], max_ticks: int = 100_000
            ) -> Dict[int, List[int]]:
        """Serve a whole trace: each request is submitted at its
        ``arrival`` tick; runs until every request finished.  Returns
        ``{rid: generated tokens}``."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        i = 0
        while i < len(pending) or not self.done:
            if self.tick > max_ticks:
                raise RuntimeError(
                    f"serve trace did not converge in {max_ticks} ticks")
            while i < len(pending) and pending[i].arrival <= self.tick:
                self.submit(pending[i])
                i += 1
            self.step()
        self.finish()
        return {s.rid: list(s.out_tokens) for s in self.sequences}

    def finish(self) -> None:
        """Fence both dispatch lanes (``clFinish`` on each)."""
        self.q_admit.finish()
        self.q_decode.finish()


__all__ = ["ServeEngine", "PER_REQUEST_CODES", "INSERT_EVENT",
           "PAGE_INSERT_EVENT", "SWAP_OUT_EVENT", "SWAP_IN_EVENT",
           "SCRUB_EVENT", "PREFIX_GATHER_EVENT", "COW_EVENT"]
