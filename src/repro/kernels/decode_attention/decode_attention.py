"""Fused decode attention forward — Pallas TPU kernel.

One ``pallas_call`` performs, per decode step:

* the ring-buffer KV-cache write: the step's K/V row lands in slot
  ``widx = pos mod S`` (the cache outputs alias the cache inputs, so on
  TPU this is an in-place update; the slot's block is rewritten by the
  grid cell that owns it),
* single-query attention of the ``group = Hq/Hkv`` query heads of each KV
  head over the *updated* cache, masked by the absolute positions stored
  alongside the cache (``pos_cache`` — slot validity is data, not layout).

Grid: ``(B, Hkv, S/block_kv)`` — all three dimensions parallel
(flash-decode split-S).  Each cell emits a partial ``(acc, m, l)`` online
softmax triple for its KV span; ``_combine_jit`` merges the splits with
the standard cross-block combine.  This is the shape that keeps a
32k-entry cache attention on all cores instead of one sequential kv loop.

Layouts obey the chip's tiling rule (the last two dims of every block are
divisible by (8, 128) or equal to the array's): the position plane is
viewed as ``(B, nsplit, 1, block_kv)`` so each cell's ``(1, block_kv)``
row is a whole trailing tile, and the partials are emitted as
``(B, Hkv, nsplit, group, ·)`` so each cell writes a whole trailing
``(group, ·)`` tile, for any ``nsplit``.

The scalar-prefetch argument carries the per-sequence ``(2, B)`` plane
``[widx[b], pos[b]]`` so index maps and the in-block row select are known
before the body runs; each grid cell reads the row of the batch it owns.
Per-sequence positions are what continuous batching needs: every sequence
in the batch may sit at a different decode depth (``pos[b] = -1`` marks an
inactive slot — all keys masked, output garbage by construction).

VMEM budget at defaults (block_kv=256, d=128, bf16 cache / f32 math):
k/v 2·256·128·2 + q/acc 2·group·128·4 + partials ≈ 0.2 MiB — far below
the flash-attention kernel's footprint, so block_kv can grow with S.

The paged kernel (``decode_attention_paged_pallas``) has its own grid
and body.  Grid ``(B, n_ptes)``: one cell per row and logical ring page,
covering all ``Hkv`` heads, so a K or V block ``(1, Hkv, page_size, D)``
is one whole physical page of the ``(n_pages, Hkv, page_size, D)``
arena.  The query groups of the heads are one batched contraction over
``Hkv``; the online softmax accumulates across the row's pages in VMEM
scratch (the page axis is sequential), so there are no split partials
and no combine.  Row ``b`` walks logical pages ``0 ..= last[b]``,
``last = min(max(pos, 0), W − 1) // page_size``: the page holding the
new token, or every page once the ring has wrapped.  The index maps
clamp later cells to ``last``, so they repeat a block index (no DMA)
and their body does nothing.  Write-back: the K/V outputs alias the
arenas and their block index is pinned, for the whole row, to the page
that takes the new token; the cell that visits it fills the block once
(the fetched page with the new row put in), so each row stores one page
and no other page is written.  Idle rows (``pos = -1``, all-null
tables) store the null page, whose stored positions stay ``-1``.
VMEM at Qwen3 widths (Hkv 8, D 128, 128-position pages, bf16): K and V
blocks 256 KiB each, double-buffered in and out ≈ 2 MiB, plus their f32
copies in the body (1 MiB) and ≈ 40 KiB of softmax scratch.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANES, NEG_INF, aligned_block


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, pos_ref,
                   ok_ref, ov_ref, o_ref, m_ref, l_ref, *,
                   scale: float, window: Optional[int], block_kv: int):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    widx = idx_ref[0, bi]
    q_pos = idx_ref[1, bi]
    blk_start = si * block_kv

    k = k_ref[0, 0]                                   # (block_kv, d)
    v = v_ref[0, 0]
    # fused cache write: overwrite the ring slot if it falls in this block
    row = jax.lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0) + blk_start
    sel = row == widx                                  # (block_kv, 1)
    k = jnp.where(sel, kn_ref[0, 0].astype(k.dtype), k)
    v = jnp.where(sel, vn_ref[0, 0].astype(v.dtype), v)
    ok_ref[0, 0] = k
    ov_ref[0, 0] = v

    # attention over the updated block, masked by stored absolute position
    q = q_ref[0, :, 0, :].astype(jnp.float32)          # (group, d)
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (group, block_kv)
    kpos = pos_ref[0, 0]                               # (1, block_kv)
    mask = (kpos >= 0) & (kpos <= q_pos)
    if window is not None:
        mask &= kpos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)              # (group, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    acc = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (group, d)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = jnp.broadcast_to(m, (m.shape[0], LANES))
    l_ref[0, 0, 0] = jnp.broadcast_to(l, (l.shape[0], LANES))


# Trace counter for the combine stage (tests assert the sweep-reuse
# property below); incremented each time JAX actually traces the body.
_combine_traces = 0


def _combine_body(q, o_part, m_part, l_part):
    """Flash-decode second stage (cheap in XLA):
    out = Σ_s exp(m_s − M) acc_s / Σ_s exp(m_s − M) l_s.  Partials are
    ``(B, Hkv, nsplit, group, ·)``; query head ``h·group + g`` is row
    ``g`` of KV head ``h``."""
    global _combine_traces
    _combine_traces += 1
    B, Hq, _, D = q.shape
    m = m_part[..., 0]                                 # (B, Hkv, nsplit, g)
    l = l_part[..., 0]
    m_glob = jnp.max(m, axis=2, keepdims=True)
    alpha = jnp.exp(m - m_glob)
    denom = jnp.maximum(jnp.sum(l * alpha, axis=2), 1e-30)  # (B, Hkv, g)
    out = jnp.sum(o_part * alpha[..., None], axis=2) / denom[..., None]
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


# Module-level jit: the combine's trace is keyed by the partial-tensor
# avals — i.e. by (num_splits,) for fixed (B, Hq, D) — and cached across
# callers.  Distinct cache lengths that resolve to the same split count
# (an autotune sweep walking block_kv at one shape-bucket rung, or two
# rungs whose S/block_kv coincide) share one traced combine instead of
# re-tracing it inside every kernel wrapper, so sweeps don't inflate the
# engine's ``stats["compiles"]`` accounting.
_combine_jit = jax.jit(_combine_body)


def decode_attention_pallas(
        q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
        pos_cache: jax.Array, k_new: jax.Array, v_new: jax.Array,
        widx: jax.Array, pos: jax.Array, *,
        window: Optional[int] = None, scale: Optional[float] = None,
        block_kv: int = 256, interpret: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused decode step.

    q: (B, Hq, 1, D); k_cache/v_cache: (B, Hkv, S, D); pos_cache: (B, S)
    int32 *already updated* with ``pos[b]`` at slot ``widx[b]``;
    k_new/v_new: (B, Hkv, 1, D); widx/pos: (B,) int32 per-sequence ring
    indices and absolute positions.

    Returns ``(out (B, Hq, 1, D), new_k_cache, new_v_cache)`` where the new
    caches alias the inputs (in-place ring write on TPU).
    """
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert T == 1, "decode kernel is single-query"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    block_kv = aligned_block(S, block_kv)
    nsplit = S // block_kv
    grid = (B, Hkv, nsplit)

    widx = jnp.broadcast_to(jnp.asarray(widx, jnp.int32), (B,))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    idx = jnp.stack([widx, pos])                       # (2, B)

    q_spec = pl.BlockSpec((1, group, 1, D), lambda b, h, s, i: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, D),
                           lambda b, h, s, i: (b, h, s, 0))
    new_spec = pl.BlockSpec((1, 1, 1, D), lambda b, h, s, i: (b, h, 0, 0))
    pos_spec = pl.BlockSpec((1, 1, 1, block_kv),
                            lambda b, h, s, i: (b, s, 0, 0))
    o_spec = pl.BlockSpec((1, 1, 1, group, D),
                          lambda b, h, s, i: (b, h, s, 0, 0))
    ml_spec = pl.BlockSpec((1, 1, 1, group, LANES),
                           lambda b, h, s, i: (b, h, s, 0, 0))

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_kv=block_kv)

    ok, ov, o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec, new_spec, new_spec,
                      pos_spec],
            out_specs=[kv_spec, kv_spec, o_spec, ml_spec, ml_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, group, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, group, LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, group, LANES),
                                 jnp.float32),
        ],
        # flattened arg indices include the scalar-prefetch array (0):
        # q=1, k_cache=2, v_cache=3 → outputs new_k=0, new_v=1
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(idx, q, k_cache, v_cache, k_new, v_new,
      pos_cache.reshape(B, nsplit, 1, block_kv))

    return _combine_jit(q, o_part, m_part, l_part), ok, ov


def last_live_page(pos, ring_width: int, page_size: int):
    """Last logical page the paged kernel walks for each row at decode
    position ``pos`` (NumPy or JAX array): the page holding ``pos`` while
    the ring has not wrapped, every page once it has, page 0 for an
    inactive row (``pos = -1``).  Pages past it hold no key the row may
    attend to, so the kernel neither fetches nor computes them."""
    return pos.clip(0, ring_width - 1) // page_size


def _paged_decode_kernel(idx_ref, pt_ref, q_ref, k_ref, v_ref, kn_ref,
                         vn_ref, pos_ref, ok_ref, ov_ref, o_ref,
                         m_sc, l_sc, acc_sc, *, scale: float,
                         window: Optional[int], page_size: int):
    """One grid cell = one row ``b`` and one logical page ``t``, all KV
    heads.  ``idx_ref`` rows: in-page write slot, query position, last
    live logical page, logical write page.  The online softmax runs
    across the row's pages in VMEM scratch (the page axis is sequential);
    cells past the last live page do nothing."""
    del pt_ref   # consumed by the index maps
    bi = pl.program_id(0)
    t = pl.program_id(1)
    woff = idx_ref[0, bi]
    q_pos = idx_ref[1, bi]
    last = idx_ref[2, bi]
    wpage = idx_ref[3, bi]

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(t <= last)
    def _page():
        k = k_ref[0]                                   # (Hkv, ps, d)
        v = v_ref[0]
        # fused cache write: the new row goes into the write page, and
        # that page alone is stored back (the K/V outputs' block index
        # is pinned to it for the whole row)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, page_size, 1), 1)
        sel = (row == woff) & (t == wpage)             # (1, ps, 1)
        k = jnp.where(sel, kn_ref[0].astype(k.dtype), k)
        v = jnp.where(sel, vn_ref[0].astype(v.dtype), v)

        @pl.when(t == wpage)
        def _store():
            ok_ref[0] = k
            ov_ref[0] = v

        # attention of each KV head's query group over the page, masked
        # by stored absolute position
        q = q_ref[0].astype(jnp.float32)               # (Hkv, group, d)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (Hkv, group, ps)
        kpos = pos_ref[0]                              # (1, 1, ps)
        mask = (kpos >= 0) & (kpos <= q_pos)
        if window is not None:
            mask &= kpos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, :, :1]                        # (Hkv, group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_sc[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # (Hkv, group, d)
        acc_sc[...] = alpha * acc_sc[...] + pv
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(t == last)
    def _finish():
        denom = jnp.maximum(l_sc[:, :, :1], 1e-30)
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def decode_attention_paged_pallas(
        q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
        pos_arena: jax.Array, k_new: jax.Array, v_new: jax.Array,
        page_table: jax.Array, widx: jax.Array, pos: jax.Array, *,
        window: Optional[int] = None, scale: Optional[float] = None,
        interpret: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused decode step over the paged KV pool.

    q: (B, Hq, 1, D); k_arena/v_arena: (n_pages, Hkv, page_size, D) pools
    shared by every sequence; pos_arena: (n_pages, page_size) int32
    *already updated* with ``pos[b]`` at the write slot; page_table:
    (B, n_ptes) int32 (entry 0 = null page); widx/pos: (B,) int32 logical
    ring indices (``pos mod W``, ``W = n_ptes·page_size``) and absolute
    positions.

    Grid: ``(B, n_ptes)``, one cell per row and *logical* ring page with
    every KV head; the scalar-prefetched page table resolves it to a
    physical arena page in the index maps.  Row ``b`` walks its live
    pages ``0 ..= last[b]`` (``last = min(max(pos, 0), W − 1) // ps``, so
    a wrapped ring keeps every page live); the index maps clamp ``t`` to
    ``last``, so later cells fetch nothing.  The K/V outputs alias the
    arenas and their block is pinned to the row's write page, stored
    once.  An inactive row (``pos = −1``) must carry an all-null table:
    its write page is clamped to logical page 0, i.e. the null page,
    whose stored positions stay ``-1``.

    Returns ``(out (B, Hq, 1, D), new_k_arena, new_v_arena)``.
    """
    B, Hq, T, D = q.shape
    n_pages, Hkv, ps, _ = k_arena.shape
    n_ptes = page_table.shape[-1]
    assert T == 1, "decode kernel is single-query"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    W = n_ptes * ps

    widx = jnp.broadcast_to(jnp.asarray(widx, jnp.int32), (B,))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    last = last_live_page(pos, W, ps)
    wpage = jnp.minimum(widx // ps, last)
    idx = jnp.stack([widx % ps, pos, last, wpage])     # (4, B)
    pt = page_table.astype(jnp.int32)

    def page(b, t, i, p):
        return (p[b, jnp.minimum(t, i[2, b])], 0, 0, 0)

    q_spec = pl.BlockSpec((1, Hkv, group, D), lambda b, t, i, p: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, Hkv, ps, D), page)
    out_kv_spec = pl.BlockSpec((1, Hkv, ps, D),
                               lambda b, t, i, p: (p[b, i[3, b]], 0, 0, 0))
    new_spec = pl.BlockSpec((1, Hkv, 1, D), lambda b, t, i, p: (b, 0, 0, 0))
    pos_spec = pl.BlockSpec((1, 1, 1, ps), page)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               window=window, page_size=ps)

    ok, ov, out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_ptes),
            in_specs=[q_spec, kv_spec, kv_spec, new_spec, new_spec,
                      pos_spec],
            out_specs=[out_kv_spec, out_kv_spec, q_spec],
            scratch_shapes=[pltpu.VMEM((Hkv, group, LANES), jnp.float32),
                            pltpu.VMEM((Hkv, group, LANES), jnp.float32),
                            pltpu.VMEM((Hkv, group, D), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
            jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype),
            jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        ],
        # flattened arg indices include both scalar-prefetch arrays
        # (idx=0, pt=1): q=2, k_arena=3, v_arena=4 → outputs 0, 1
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx, pt, q.reshape(B, Hkv, group, D), k_arena, v_arena, k_new, v_new,
      pos_arena.reshape(n_pages, 1, 1, ps))

    return out.reshape(B, Hq, 1, D), ok, ov


__all__ = ["decode_attention_pallas", "decode_attention_paged_pallas",
           "last_live_page"]
