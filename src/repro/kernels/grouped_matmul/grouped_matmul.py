"""Grouped matmul forward — Pallas TPU kernel.

Rows of ``lhs`` (m, k) are sorted by group; group ``g`` owns rows
``[offsets[g], offsets[g+1])`` and multiplies them by ``rhs[layer, g]``
(k, n).  ``rhs`` (C, G, k, n) is the expert stack of every repeat of a
scanned layer group and ``layer`` the repeat being run: the kernel reads
its weights out of the whole stack (the index map adds ``layer · G``), so
a scan step copies no expert matrices out of the stack before the call.

Visit plan (``plan_visits``): a *visit* is one (group, row tile) pair in
which the group holds at least one row of the ``tm``-row tile.  Visits
are ordered by group, so row tiles come in non-decreasing order and a
tile that several groups share is visited on consecutive grid steps —
its output block stays resident in VMEM between them and each visit
stores only its own group's rows.  At most ``m/tm + G - 1`` visits exist;
the grid has that many (static), and cells past the plan's real count
repeat the last real visit's block indices (no DMA) and skip the body.
An empty group has no visit, so its weights are never read.

Grid ``(n/tn, visits, k/tk)``: the ``n`` axis parallel, visits and the
contraction sequential (f32 accumulator in VMEM scratch).  At the
expert widths served here ``tk = k`` and ``tn = n`` when a weight block
fits ``RHS_BLOCK_BYTES``, so one visit is one grid step that reads one
expert's whole matrix once.

VMEM at Mellum2 widths (d 2304, expert width 896, bf16, tm 128): a weight
block 4.1 MiB, double-buffered 8.3 MiB, plus lhs and out tiles
(≤ 1.2 MiB double-buffered) and the f32 accumulator (≤ 1.2 MiB).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANES

RHS_BLOCK_BYTES = 6 << 20       # largest weight block read in one DMA
VMEM_LIMIT_BYTES = 48 << 20


class Visits(NamedTuple):
    """The visit plan of one grouped matmul (shared by the matmuls of one
    expert layer): per visit its group and row tile, the group row
    offsets, and the number of real visits."""
    group: jax.Array       # (V,) int32
    tile: jax.Array        # (V,) int32
    offsets: jax.Array     # (G + 1,) int32
    count: jax.Array       # (1,) int32


def plan_visits(group_sizes: jax.Array, m: int, tm: int) -> Visits:
    """Visits of the row tiles of an ``m``-row lhs (``m % tm == 0``) by
    the groups of ``group_sizes``; plain vectorised jnp (no loops)."""
    G = group_sizes.shape[0]
    tiles = m // tm
    V = tiles + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    last = (jnp.maximum(ends, 1) - 1) // tm
    per = jnp.where(sizes > 0, last - first + 1, 0)       # visits a group
    cum = jnp.cumsum(per)
    count = cum[-1]
    v = jnp.arange(V, dtype=jnp.int32)
    # clamp cells past the real visits onto the last real one
    vc = jnp.minimum(v, jnp.maximum(count - 1, 0))
    group = jnp.sum(cum[None, :] <= vc[:, None], axis=1).astype(jnp.int32)
    group = jnp.minimum(group, G - 1)
    tile = first[group] + vc - (cum[group] - per[group])
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return Visits(group, tile, offsets, count.reshape(1).astype(jnp.int32))


def pick_tiles(k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """``(tk, tn)``: the whole contraction and output width when one
    weight block fits ``RHS_BLOCK_BYTES``, else the output width split
    into lane-aligned parts, then the contraction."""
    tk, tn = k, n
    while tk * tn * itemsize > RHS_BLOCK_BYTES and tn % (2 * LANES) == 0:
        tn //= 2
    while tk * tn * itemsize > RHS_BLOCK_BYTES and tk % (2 * LANES) == 0:
        tk //= 2
    return tk, tn


def _gmm_kernel(group_ref, tile_ref, off_ref, count_ref, base_ref, lhs_ref,
                rhs_ref, out_ref, acc_ref, *, tm: int, k_steps: int):
    v = pl.program_id(1)
    ki = pl.program_id(2)
    live = v < count_ref[0]

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(live & (ki == k_steps - 1))
    def _store():
        g = group_ref[v]
        row = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0) \
            + tile_ref[v] * tm
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def grouped_matmul_pallas(lhs: jax.Array, rhs: jax.Array, visits: Visits,
                          layer: jax.Array, *, tm: int, out_dtype,
                          interpret: bool = False) -> jax.Array:
    """lhs (m, k) sorted by group with ``m % tm == 0``; rhs (C, G, k, n)
    and ``layer`` (1,) int32 the repeat of the stack to use; ``visits``
    from :func:`plan_visits` at the same ``m`` and ``tm``.  Returns
    (m, n); rows in no group are unspecified."""
    m, k = lhs.shape
    C, G, _, n = rhs.shape
    assert m % tm == 0, (m, tm)
    rhs = rhs.reshape(C * G, k, n)        # merges major dims: a bitcast
    base = (layer.astype(jnp.int32) * G).reshape(1)
    tk, tn = pick_tiles(k, n, rhs.dtype.itemsize)
    V = visits.group.shape[0]
    grid = (n // tn, V, k // tk)

    def lhs_map(ni, v, ki, group, tile, off, count, base):
        return tile[v], ki

    def rhs_map(ni, v, ki, group, tile, off, count, base):
        return base[0] + group[v], ki, ni

    def out_map(ni, v, ki, group, tile, off, count, base):
        return tile[v], ni

    kernel = functools.partial(_gmm_kernel, tm=tm, k_steps=k // tk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(visits.group, visits.tile, visits.offsets, visits.count, base, lhs,
      rhs)


__all__ = ["Visits", "plan_visits", "pick_tiles", "grouped_matmul_pallas",
           "RHS_BLOCK_BYTES"]
