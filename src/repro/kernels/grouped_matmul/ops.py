"""Public op: grouped matmul over expert-sorted rows, Pallas or XLA.

``impl="pallas"`` (or ``"auto"``) runs the visit-plan kernel
(``grouped_matmul.py``; interpret mode on the CPU); ``impl="xla"`` runs
the ``ragged_dot`` oracle.  The expert layer passes its config's
``attn_impl``, which chooses every Pallas kernel of a model.  The kernel's custom call is the jitted
``_moe_gmm`` below, which is the name the device trace shows.  Its
backward pass is the oracle's, so training differentiates through the
same routing (the kernel is forward-only).

``plan`` is shared by every matmul over the same rows (the gate, up and
down projections of one expert layer): build it once with
:func:`make_plan` and pass it to each call.

``rhs`` is one layer's experts (G, k, n), or the stack of every repeat of
a scanned layer group (C, G, k, n) with ``layer`` the repeat to use: the
kernel then reads that repeat's experts straight out of the stack, where
slicing it first would copy the whole stack before every call.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..common import interpret_mode
from .grouped_matmul import Visits, grouped_matmul_pallas, plan_visits
from .ref import grouped_matmul_ref

TILE_M = 128          # rows per tile (a multiple of bf16's 16-row tile)


class Plan(NamedTuple):
    group_sizes: jax.Array     # (G,) int32
    visits: Optional[Visits]   # None for impl="xla"
    tm: int


def row_tile(m: int) -> int:
    """Rows per tile for an ``m``-row lhs: ``TILE_M``, or all rows
    rounded up to 16 when fewer."""
    return min(TILE_M, -(-m // 16) * 16)


def padded_rows(m: int) -> int:
    """Rows the lhs is padded to: a multiple of :func:`row_tile`."""
    tm = row_tile(m)
    return -(-m // tm) * tm


def make_plan(group_sizes: jax.Array, m: int, impl: str) -> Plan:
    """The visit plan of an ``m``-row lhs (``m`` already padded, see
    :func:`padded_rows`) under ``impl``."""
    tm = row_tile(m)
    if impl == "xla":
        return Plan(group_sizes, None, tm)
    return Plan(group_sizes, plan_visits(group_sizes, m, tm), tm)


@functools.partial(jax.jit, static_argnames=("tm", "out_dtype"))
def _moe_gmm(lhs, rhs, visits, layer, tm, out_dtype):
    return grouped_matmul_pallas(lhs, rhs, visits, layer, tm=tm,
                                 out_dtype=jnp.dtype(out_dtype),
                                 interpret=interpret_mode())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gmm(lhs, rhs, group_sizes, visits, layer, tm, out_dtype):
    return _moe_gmm(lhs, rhs, visits, layer, tm=tm, out_dtype=out_dtype)


def _gmm_fwd(lhs, rhs, group_sizes, visits, layer, tm, out_dtype):
    return _gmm(lhs, rhs, group_sizes, visits, layer, tm, out_dtype), \
        (lhs, rhs, group_sizes, layer)


def _gmm_bwd(tm, out_dtype, res, g):
    lhs, rhs, group_sizes, layer = res
    _, vjp = jax.vjp(lambda a, b: grouped_matmul_ref(
        a, b[layer[0]], group_sizes, out_dtype=jnp.float32), lhs, rhs)
    d_lhs, d_rhs = vjp(g.astype(jnp.float32))
    return d_lhs, d_rhs, None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, plan: Plan,
                   out_dtype=None, layer=None) -> jax.Array:
    """``lhs`` (m, k), rows sorted by group and ``m`` a multiple of
    ``plan.tm``; ``rhs`` (G, k, n), or (C, G, k, n) with ``layer`` (an
    int32 scalar) the repeat to use.  Row ``r`` of group ``g`` gives
    ``lhs[r] @ rhs[layer, g]``, accumulated in float32, in ``out_dtype``
    (default lhs dtype); rows past ``sum(group_sizes)`` are unspecified
    under the kernel and zero under the oracle."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if plan.visits is None:
        return grouped_matmul_ref(lhs, rhs[layer[0]], plan.group_sizes,
                                  out_dtype)
    return _gmm(lhs, rhs, plan.group_sizes, plan.visits, layer, plan.tm,
                out_dtype.name)


__all__ = ["grouped_matmul", "make_plan", "padded_rows", "row_tile",
           "Plan", "TILE_M"]
