"""Serve-step factories: prefill (context → cache + first logits) and
decode (one token against a standing cache).

Ring-buffer alignment: prefill collects full-sequence K/V (slot j =
absolute position j); ``align_prefill_cache`` re-lays it out as the
standing decode ring sized by the decode *budget* — slot j holds absolute
position ≡ j (mod W) where ``W = cfg.cache_len(kind, budget)``, the
invariant every subsequent decode write (``widx = pos mod W``) maintains.
The gather/pad indices are static, so this is one copy (the old scheme
paid a slice *and* a ``jnp.roll``), and the absolute positions travel in
``KVCache.pos`` so the decode kernel masks validity by data rather than
layout.  Because the layout depends only on the budget (not the prompt
length), prefills of any length are slot-compatible with
``model.cache_init(cfg, B, budget)`` — ``cache_slot_insert`` /
``cache_slot_extract`` move batch=1 caches in and out of a standing
batched cache, which is what the continuous-batching engine
(``serve/engine``) builds on.

The step factories are cached on the (hashable, frozen) config — repeated
``make_prefill_step``/``make_decode_step`` calls return the *same* jitted
callable, so servers that rebuild steps per request never retrace.
``DECODE_EVENT``/``PREFILL_EVENT`` are the canonical event names for
dispatch-queue submissions, letting the profiler aggregate decode traffic
separately from prefill.

**Shape buckets** (DESIGN.md "Shape discipline & bucketing"): the legacy
factories above still trace one program per *exact* input shape — every
distinct prompt length retraces the prefill jit and the decode step is
pinned at the full slot width.  :class:`BucketRegistry` replaces them for
the serve engine: every jitted step runs at a shape drawn from a small
static ladder — decode widths in powers of two up to ``n_slots``
(:func:`width_ladder`), prompt lengths rounded up to a page-aligned
geometric ladder (:func:`length_ladder`) with ``pos = -1`` masking the
padding — so a trace with thousands of distinct prompt lengths compiles
at most ``len(ladder)`` prefill programs.  The registry wraps each step
to detect actual traces (jit cache-size delta), recording a
``TRACE_COMPILE`` profiler event and a per-kind compile count that the
engine surfaces as ``stats()["compiles"]``.  The registry's prefill and
decode steps return ``(logits, cache, moe)``: ``moe`` is the expert
layers' ``(layers, 2)`` int32 routing counts (``model.Aux.moe``), which
the engine brings to the host with the logits.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.event import Event
from ..dist.sharding import ShardCtx, use_ctx
from ..models import model as M
from ..models.attention import KVCache

PREFILL_EVENT = "PREFILL_KERNEL"
DECODE_EVENT = "DECODE_KERNEL"
ALIGN_EVENT = "ALIGN_CACHE"
TRACE_COMPILE_EVENT = "TRACE_COMPILE"
TRACE_AUTOTUNE_EVENT = "TRACE_AUTOTUNE"


def _build_prefill_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx] = None):
    pcfg = dataclasses.replace(cfg, collect_kv=True)

    def prefill_step(params, tokens, ctx_embed=None):
        with use_ctx(ctx):
            hidden, cache, _ = M.forward(pcfg, params, tokens,
                                         ctx_embed=ctx_embed)
            logits = M.logits_fn(pcfg, params, hidden[:, -1:])
        return logits, cache

    return jax.jit(prefill_step)


def _build_decode_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx] = None):
    def decode_step(params, cache, token, pos):
        with use_ctx(ctx):
            return M.decode_step(cfg, params, cache, token, pos)

    return jax.jit(decode_step)


def _prefix_len(cfg: M.ModelConfig, prefix_cache: Dict) -> int:
    """Static prefix length of a collect_kv-layout cache: the ring axis
    of its first attention leaf (all kinds carry the same full-page
    prefix span)."""
    for gi, (kinds, _) in enumerate(M.cache_layout(cfg)):
        for pi, kind in enumerate(kinds):
            if kind in M.KV_KINDS:
                return prefix_cache["groups"][gi][pi].k.shape[-2]
    raise AssertionError("prefix cache has no attention leaves")


def _build_prefill_ext_step(cfg: M.ModelConfig,
                            ctx: Optional[ShardCtx] = None):
    pcfg = dataclasses.replace(cfg, collect_kv=True)

    def prefill_ext_step(params, tokens, prefix_cache):
        s = _prefix_len(pcfg, prefix_cache)
        with use_ctx(ctx):
            hidden, cache, _ = M.forward(pcfg, params, tokens,
                                         cache=prefix_cache,
                                         pos0=jnp.int32(s))
            logits = M.logits_fn(pcfg, params, hidden[:, -1:])
        return logits, cache

    return jax.jit(prefill_ext_step)


_cached_prefill = functools.cache(_build_prefill_step)
_cached_decode = functools.cache(_build_decode_step)
_cached_prefill_ext = functools.cache(_build_prefill_ext_step)


def make_prefill_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx] = None):
    """Jitted prefill step; cached on ``(cfg, ctx)`` — ``ShardCtx`` hashes
    by identity, so servers that rebuild steps per request never retrace
    as long as they hold on to their context (as they should: the cache
    retains every distinct ctx and its compiled step for the process
    lifetime, so churning fresh ShardCtx objects leaks executables)."""
    return _cached_prefill(cfg, ctx)


def make_decode_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx] = None):
    """Jitted decode step, ``(params, cache, token, pos) → (logits, cache,
    moe)`` (``model.decode_step``); cached on ``(cfg, ctx)`` (see
    :func:`make_prefill_step`).  The serve engine's full-width decode is
    this same program."""
    return _cached_decode(cfg, ctx)


def make_prefill_ext_step(cfg: M.ModelConfig,
                          ctx: Optional[ShardCtx] = None):
    """Jitted *partial* prefill: ``(params, tokens, prefix_cache) →
    (last-token logits, full-span collected cache)``.

    ``prefix_cache`` is a batch=1 collect_kv-layout cache of the first
    ``s`` prompt positions (prefix sharing gathers it straight from the
    paged pool's shared pages); ``tokens`` are the remaining prompt
    ``[s:]``, consumed at positions ``s..L-1`` while attending over
    prefix + fresh keys.  The returned cache covers the whole ``[0, L)``
    span, so ring alignment and page donation are identical to the
    one-shot prefill.  Cached on ``(cfg, ctx)``; distinct ``(s, L-s)``
    shapes retrace, like distinct prompt lengths do (documented engine
    simplification)."""
    return _cached_prefill_ext(cfg, ctx)


def _build_align_step(cfg: M.ModelConfig, seq_len: int,
                      target_len: Optional[int],
                      page_size: Optional[int]):
    if page_size is None:
        return jax.jit(
            lambda cache: align_prefill_cache(cfg, cache, seq_len,
                                              target_len))

    from .paging import ring_to_page_blocks  # circular-import guard

    def align_paged(cache):
        aligned = align_prefill_cache(cfg, cache, seq_len, target_len)
        return ring_to_page_blocks(cfg, aligned, page_size)

    return jax.jit(align_paged)


_cached_align = functools.cache(_build_align_step)


def make_align_step(cfg: M.ModelConfig, seq_len: int,
                    target_len: Optional[int] = None,
                    page_size: Optional[int] = None):
    """Jitted prefill→decode cache relayout (one fused program instead of
    eager per-layer gathers/pads); cached on (cfg, lengths, page_size).

    With ``page_size`` set, the aligned ring is additionally cut into
    page blocks (``paging.ring_to_page_blocks``) — the form the paged
    pool's admission scatter consumes, fused into the same program."""
    return _cached_align(cfg, seq_len, target_len, page_size)


def _ring_gather_idx(seq_len: int, W: int) -> np.ndarray:
    """Static source indices: slot j ← the newest prefill position p < L
    with p ≡ j (mod W); all gathered p lie in [L - W, L)."""
    base = seq_len - W
    return np.array([base + ((j - base) % W) for j in range(W)])


def align_prefill_cache(cfg: M.ModelConfig, cache: Dict, seq_len: int,
                        target_len: Optional[int] = None) -> Dict:
    """Convert prefill-collected caches (slot j = absolute position j,
    length ``seq_len``) to the standing decode (ring) layout sized by the
    decode budget ``target_len`` (default: ``seq_len``).

    Every cache kind lands in a ring of width
    ``W = cfg.cache_len(kind, budget)`` — the *same* width
    ``model.cache_init(cfg, B, budget)`` allocates, so prefills of any
    prompt length produce slot-compatible caches for a given budget
    (what lets the serve engine pack per-request prefills into a standing
    batched cache via :func:`cache_slot_insert`):

    * ``W < seq_len``: one static gather puts the last ``W`` positions
      into ring order (slot j ≡ position j mod W) — no ``jnp.roll``;
    * ``W > seq_len``: pad with unwritten slots (``pos = -1``, masked by
      the position test); existing slots already satisfy the invariant
      (position j sits in slot j = j mod W).
    """
    # explicit None test: ``target_len or seq_len`` would silently turn a
    # caller's (buggy) target_len=0 into "no target"
    if target_len is None:
        budget = seq_len
    else:
        assert target_len >= 1, \
            f"target_len must be a positive decode budget, got {target_len}"
        budget = target_len
    assert budget >= seq_len, \
        f"decode budget {budget} smaller than the prefill ({seq_len}): " \
        "full-attention positions would be silently dropped"
    out = {k: v for k, v in cache.items() if k != "groups"}
    groups = []
    for gi, (kinds, count) in enumerate(M.cache_layout(cfg)):
        pos_caches = []
        for pi, kind in enumerate(kinds):
            c = cache["groups"][gi][pi]
            if kind in M.KV_KINDS and isinstance(c, KVCache):
                W = cfg.cache_len(kind, budget)
                S = c.k.shape[-2]
                if W < S:  # ring buffer narrower than the prefill
                    src = _ring_gather_idx(seq_len, W)
                    c = KVCache(jnp.take(c.k, src, axis=-2),
                                jnp.take(c.v, src, axis=-2),
                                None if c.pos is None
                                else jnp.take(c.pos, src, axis=-1))
                elif W > S:  # budget beyond the prefill: unwritten slots
                    pad = [(0, 0)] * c.k.ndim
                    pad[-2] = (0, W - S)
                    ppad = [(0, 0)] * (c.k.ndim - 2)
                    ppad[-1] = (0, W - S)
                    c = KVCache(jnp.pad(c.k, pad), jnp.pad(c.v, pad),
                                None if c.pos is None
                                else jnp.pad(c.pos, ppad,
                                             constant_values=-1))
            pos_caches.append(c)
        groups.append(tuple(pos_caches))
    out["groups"] = groups
    return out


# --------------------------------------------------- shape bucketing ------

def width_ladder(n_slots: int) -> Tuple[int, ...]:
    """Decode width buckets: powers of two up to ``n_slots``, plus
    ``n_slots`` itself (the classic full-width step)."""
    assert n_slots >= 1, n_slots
    out, w = [], 1
    while w < n_slots:
        out.append(w)
        w *= 2
    out.append(n_slots)
    return tuple(out)


def length_ladder(quantum: int, max_len: int) -> Tuple[int, ...]:
    """Prompt length buckets: a geometric (×2) ladder of multiples of
    ``quantum`` (the page size in paged mode) whose last rung covers
    ``max_len`` — the decode budget, since admission rejects longer
    prompts."""
    assert quantum >= 1 and max_len >= 1, (quantum, max_len)
    out, b = [], quantum
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(b)
    return tuple(out)


def _build_prefill_bucket_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx],
                               bucket_len: int):
    """Length-bucketed prefill: tokens are right-padded to the static
    ``bucket_len`` and the traced ``true_len`` drives a ``pos = -1`` mask
    over the padding — the same sentinel the ring caches use for
    unwritten slots, so the padded tail is invisible to every attention
    mask and lands in the collected cache as never-written positions.
    One compiled program serves every prompt length in the bucket."""
    pcfg = dataclasses.replace(cfg, collect_kv=True)

    def prefill_bucket(params, tokens, true_len, ctx_embed=None):
        with use_ctx(ctx):
            ar = jnp.arange(bucket_len, dtype=jnp.int32)
            positions = jnp.where(ar < true_len, ar, -1)
            hidden, cache, aux = M.forward(pcfg, params, tokens,
                                           ctx_embed=ctx_embed,
                                           positions=positions)
            # first output token falls out of the *last real* position
            last = jax.lax.dynamic_slice_in_dim(hidden, true_len - 1, 1,
                                                axis=1)
            logits = M.logits_fn(pcfg, params, last)
        return logits, cache, aux.moe

    return jax.jit(prefill_bucket)


def _build_prefill_ext_bucket_step(cfg: M.ModelConfig,
                                   ctx: Optional[ShardCtx],
                                   prefix_pad: int, tail_len: int):
    """Bucketed *partial* prefill: the gathered prefix span is padded to
    ``prefix_pad`` positions (null pages, ``pos = -1``) and the fresh
    tail to ``tail_len``; the traced ``(true_prefix, true_len)`` pair
    masks both paddings.  Replaces the per-``(s, L-s)`` retrace of
    :func:`make_prefill_ext_step` with one program per bucket pair."""
    # every collect-path impl must honor positions as *data* here (null
    # pages sit mid-array with pos = -1): the Pallas flash kernel and the
    # XLA reference both take explicit position planes, but the T>1024
    # _xla_flash fallback is causal by index — cap the XLA path's span
    assert cfg.attn_impl in ("pallas", "auto") \
        or prefix_pad + tail_len <= 1024, \
        "bucketed partial prefill on the xla impl requires the " \
        "position-masked (≤1024-key) attention path"
    pcfg = dataclasses.replace(cfg, collect_kv=True)

    def prefill_ext_bucket(params, tokens, prefix_cache, true_prefix,
                           true_len):
        with use_ctx(ctx):
            ar = jnp.arange(tail_len, dtype=jnp.int32)
            positions = jnp.where(true_prefix + ar < true_len,
                                  true_prefix + ar, -1)
            hidden, cache, aux = M.forward(pcfg, params, tokens,
                                           cache=prefix_cache,
                                           positions=positions)
            last = jax.lax.dynamic_slice_in_dim(
                hidden, true_len - true_prefix - 1, 1, axis=1)
            logits = M.logits_fn(pcfg, params, last)
        return logits, cache, aux.moe

    return jax.jit(prefill_ext_bucket)


def align_prefill_cache_dyn(cfg: M.ModelConfig, cache: Dict, true_len,
                            target_len: int, true_prefix=0,
                            prefix_pad: int = 0) -> Dict:
    """Traced-length variant of :func:`align_prefill_cache`: the collected
    cache spans a *static* bucket (ring axis ``S ≥ true_len``; slots past
    the prompt are ``pos = -1`` padding) and ``true_len`` is a traced
    scalar, so one compiled program aligns every prompt length in the
    bucket.

    Ring slot ``j`` of width ``W`` receives the newest prompt position
    ``p ≡ j (mod W)``, i.e. ``p = j + W·⌊(true_len-1-j)/W⌋``; slots with
    ``p < 0`` (budget beyond the prompt) become unwritten (``pos = -1``,
    zero K/V — bit-identical to the static path's zero padding).  With a
    bucketed shared prefix the source layout is ``[prefix_pad | tail]``:
    position ``p`` lives in slot ``p`` for ``p < true_prefix`` and slot
    ``prefix_pad + (p - true_prefix)`` past it."""
    true_len = jnp.asarray(true_len, jnp.int32)
    true_prefix = jnp.asarray(true_prefix, jnp.int32)
    out = {k: v for k, v in cache.items() if k != "groups"}
    groups = []
    for gi, (kinds, _) in enumerate(M.cache_layout(cfg)):
        leaves = []
        for pi, kind in enumerate(kinds):
            c = cache["groups"][gi][pi]
            if kind in M.KV_KINDS and isinstance(c, KVCache):
                W = cfg.cache_len(kind, target_len)
                j = jnp.arange(W, dtype=jnp.int32)
                p = j + W * jnp.floor_divide(true_len - 1 - j, W)
                valid = p >= 0           # p < true_len ≤ S by construction
                slot = jnp.where(p < true_prefix, p,
                                 p + (prefix_pad - true_prefix))
                src = jnp.where(valid, slot, 0)
                vmask = valid[:, None]
                c = KVCache(
                    jnp.where(vmask, jnp.take(c.k, src, axis=-2), 0),
                    jnp.where(vmask, jnp.take(c.v, src, axis=-2), 0),
                    None if c.pos is None else jnp.broadcast_to(
                        jnp.where(valid, p, -1),
                        c.pos.shape[:-1] + (W,)))
            leaves.append(c)
        groups.append(tuple(leaves))
    out["groups"] = groups
    return out


def _build_align_bucket_step(cfg: M.ModelConfig, ring_len: int,
                             target_len: int, page_size: Optional[int],
                             prefix_pad: int):
    """Jitted dynamic relayout (``(cache, true_len, true_prefix) → ring``
    or page blocks), cached per (cfg, bucketed span, budget, page size,
    prefix pad) — ``ring_len`` only names the bucket for the cache key;
    the traced shapes carry it."""
    del ring_len

    def align_dyn(cache, true_len, true_prefix):
        aligned = align_prefill_cache_dyn(cfg, cache, true_len, target_len,
                                          true_prefix, prefix_pad)
        if page_size is None:
            return aligned
        from .paging import ring_to_page_blocks  # circular-import guard
        return ring_to_page_blocks(cfg, aligned, page_size)

    return jax.jit(align_dyn)


def _build_decode_packed_step(cfg: M.ModelConfig, ctx: Optional[ShardCtx]):
    """Width-packed decode: gather the active slots' rows into a dense
    ``(W,)`` batch, run the ordinary decode step at width ``W``, scatter
    the results back (padding rows — ``rows == n_slots`` — are dropped).
    One builder per (cfg, ctx); jit retraces once per packed width, which
    the engine draws from :func:`width_ladder`."""
    from .paging import gather_batch_rows, scatter_batch_rows

    def decode_packed(params, cache, token, pos, rows):
        with use_ctx(ctx):
            small = gather_batch_rows(cfg, cache, rows)
            logits, new_small, moe = M.decode_step(
                cfg, params, small, token, pos)
            new_cache = scatter_batch_rows(cfg, cache, new_small, rows)
        return logits, new_cache, moe

    return jax.jit(decode_packed)


_cached_prefill_bucket = functools.cache(_build_prefill_bucket_step)
_cached_prefill_ext_bucket = functools.cache(_build_prefill_ext_bucket_step)
_cached_align_bucket = functools.cache(_build_align_bucket_step)
_cached_decode_packed = functools.cache(_build_decode_packed_step)


class BucketRegistry:
    """Shape-bucketed step registry for the serve engine.

    Keys every jitted serving step on ``(cfg, ctx, kind, shape bucket)``:
    decode widths from :func:`width_ladder`, prompt lengths from
    :func:`length_ladder` (page-aligned in paged mode), shared-prefix
    spans from a power-of-two page-count ladder.  The underlying builders
    are process-global (``functools.cache``), so engines sharing a config
    share compiled programs; per-registry instrumentation still sees
    every *trace* this registry's calls trigger — each getter wraps its
    step to compare the jit cache size around the call, recording a
    ``TRACE_COMPILE`` profiler event (bucket kind, shape, wall time) in
    :attr:`events` and bumping :attr:`compiles` when a shape actually
    compiled.

    ``bucketing=False`` degenerates to identity ladders — exact prompt
    lengths, always-full decode width — turning the registry into a pure
    compile counter for the fixed-shape baseline (benchmark E12).

    Prompt length bucketing is disabled for configs with recurrent state
    caches (ssm / rec): their prefill scans would fold the padded steps
    into the carried state.  Width packing and dynamic alignment are
    state-safe (rows move whole, padding rows are dropped) and stay on.
    """

    def __init__(self, cfg: M.ModelConfig, *, n_slots: int, budget: int,
                 page_size: Optional[int] = None,
                 prefill_cfg: Optional[M.ModelConfig] = None,
                 ctx: Optional[ShardCtx] = None, bucketing: bool = True):
        self.cfg = cfg
        self.pcfg = prefill_cfg or cfg
        self.ctx = ctx
        self.n_slots = n_slots
        self.budget = budget
        self.page_size = page_size
        self.bucketing = bool(bucketing)
        has_state = any(kind in ("ssm", "rec")
                        for kinds, _ in M.cache_layout(cfg)
                        for kind in kinds)
        self.len_bucketing = self.bucketing and not has_state
        quantum = page_size if page_size else 8
        self.widths = width_ladder(n_slots) if self.bucketing \
            else (n_slots,)
        self.lengths = length_ladder(quantum, budget) \
            if self.len_bucketing else ()
        self.compiles: Dict[str, int] = {}
        self.events: list = []
        # observer called as on_compile(kind) whenever a bucket shape
        # actually compiles (the engine feeds its compile counter)
        self.on_compile: Optional[Any] = None
        self._wrapped: Dict[tuple, Any] = {}

    # -- ladder lookups --------------------------------------------------
    def width_bucket(self, n_active: int) -> int:
        """Smallest ladder width covering ``n_active`` rows."""
        for w in self.widths:
            if w >= n_active:
                return w
        return self.n_slots

    def len_bucket(self, length: int) -> int:
        """Smallest ladder length covering ``length`` (identity when
        length bucketing is off or the prompt outruns the ladder)."""
        for b in self.lengths:
            if b >= length:
                return b
        return length

    def page_bucket(self, n_pages: int) -> int:
        """Shared-prefix page-count bucket (next power of two)."""
        if not self.len_bucketing or n_pages <= 0:
            return n_pages
        b = 1
        while b < n_pages:
            b *= 2
        return b

    # -- instrumentation -------------------------------------------------
    def _get(self, kind: str, shape: tuple, builder, *bargs):
        key = (kind,) + shape
        fn = self._wrapped.get(key)
        if fn is None:
            fn = self._instrument(kind, shape, builder(*bargs))
            self._wrapped[key] = fn
        return fn

    def _instrument(self, kind: str, shape: tuple, fn):
        def call(*args, **kwargs):
            before = fn._cache_size()
            ev = Event("Compile", TRACE_COMPILE_EVENT,
                       name=f"{TRACE_COMPILE_EVENT}:{kind}"
                            f"{list(shape) if shape else ''}")
            ev.mark_start()
            out = fn(*args, **kwargs)
            if fn._cache_size() > before:
                ev.mark_end()
                self.compiles[kind] = self.compiles.get(kind, 0) + 1
                self.events.append(ev)
                if self.on_compile is not None:
                    self.on_compile(kind)
            return out

        return call

    # -- bucketed steps --------------------------------------------------
    def prefill(self, bucket_len: int):
        return self._get("prefill", (bucket_len,), _cached_prefill_bucket,
                         self.pcfg, self.ctx, bucket_len)

    def prefill_ext(self, prefix_pad: int, tail_len: int):
        return self._get("prefill_ext", (prefix_pad, tail_len),
                         _cached_prefill_ext_bucket, self.pcfg, self.ctx,
                         prefix_pad, tail_len)

    def decode(self, width: int):
        """Packed decode at ladder width ``width < n_slots`` (one builder;
        jit retraces per width — the wrapper attributes the trace to the
        width it was called at)."""
        return self._get("decode", (width,), _cached_decode_packed,
                         self.cfg, self.ctx)

    def decode_full(self):
        """The classic full-width decode step (no gather/scatter), used
        when the covering bucket is ``n_slots`` itself."""
        return self._get("decode", (self.n_slots,), _cached_decode,
                         self.cfg, self.ctx)

    def align(self, ring_len: int, prefix_pad: int = 0):
        return self._get("align", (ring_len, prefix_pad),
                         _cached_align_bucket, self.cfg, ring_len,
                         self.budget, self.page_size, prefix_pad)


def _slot_index(leaf_ndim: int, slot, axis: int):
    # every index shares the slot's dtype (mixed int32/int64 indices are
    # a dynamic_slice error once x64 promotes the literal 0s)
    slot = jnp.asarray(slot, jnp.int32)
    idx = [jnp.zeros((), jnp.int32)] * leaf_ndim
    idx[axis] = slot
    return tuple(idx)


def cache_slot_insert(batched: Dict, one: Dict, slot) -> Dict:
    """Write a batch=1 cache into batch slot ``slot`` of a standing
    batched cache (functional; jit-able with ``slot`` traced).

    ``one`` must be laid out at the same decode budget as the standing
    cache (prefill → :func:`align_prefill_cache` with the standing
    ``target_len``), so every leaf matches except the batch axis — axis 1
    for group leaves (leading layer-stack dim), axis 0 for top-level
    entries such as ``ctx_enc``.
    """
    out = {}
    for key, dst in batched.items():
        axis = 1 if key == "groups" else 0
        out[key] = jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice(
                d, s.astype(d.dtype), _slot_index(d.ndim, slot, axis)),
            dst, one[key])
    return out


def cache_slot_extract(batched: Dict, slot) -> Dict:
    """Read batch slot ``slot`` of a standing batched cache back out as a
    batch=1 cache (inverse of :func:`cache_slot_insert`)."""
    out = {}
    for key, src in batched.items():
        axis = 1 if key == "groups" else 0

        def _take(a, axis=axis):
            sizes = list(a.shape)
            sizes[axis] = 1
            return jax.lax.dynamic_slice(
                a, _slot_index(a.ndim, slot, axis), sizes)

        out[key] = jax.tree.map(_take, src)
    return out


__all__ = ["make_prefill_step", "make_decode_step", "make_prefill_ext_step",
           "make_align_step", "align_prefill_cache",
           "align_prefill_cache_dyn", "cache_slot_insert",
           "cache_slot_extract", "BucketRegistry", "width_ladder",
           "length_ladder", "PREFILL_EVENT", "DECODE_EVENT",
           "ALIGN_EVENT", "TRACE_COMPILE_EVENT", "TRACE_AUTOTUNE_EVENT"]
