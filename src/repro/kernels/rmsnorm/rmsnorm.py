"""Fused RMSNorm — Pallas TPU kernel.

One grid step normalizes a (block_rows, d) tile held in VMEM: the mean of
squares, rsqrt and the gamma product fuse into a single VMEM-resident pass
(vs. 3 HBM round-trips unfused).  The tile spans the whole feature axis
``d`` (a block dim equal to the array's is legal at any width — SmolLM's
d_model is 960, not a multiple of 128); ``block_rows`` is a request, and
the wrapper takes the largest multiple of 8 dividing ``rows`` that does
not exceed it, or all rows (``common.aligned_block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import aligned_block


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, plus_one: bool):
    x = x_ref[...].astype(jnp.float32)                 # (bR, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    if plus_one:
        w = w + 1.0
    o_ref[...] = (y * w[None, :]).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                   plus_one: bool = False, block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    """x: (rows, d), w: (d,) → (rows, d).  Caller flattens leading dims."""
    rows, d = x.shape
    assert w.shape == (d,)
    block_rows = aligned_block(rows, block_rows)
    grid = (rows // block_rows,)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, plus_one=plus_one)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=(pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))),
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, w)


__all__ = ["rmsnorm_pallas"]
