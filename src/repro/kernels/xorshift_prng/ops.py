"""Jitted public ops for the massive-PRNG kernels.

API mirrors the example app's needs: ``prng_init(n)`` seeds state for ``n``
64-bit values, ``prng_step(state)`` produces the next batch (Listing S5),
``to_uint64``/``to_uniform`` convert the (hi, lo) planes for consumers.
On the CPU backend the Pallas kernels run in ``interpret=True`` mode
(``common.interpret_mode``, asked at trace time); on a TPU the same
BlockSpec'd kernels compile natively.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import interpret_mode
from . import ref as _ref
from .xorshift_prng import DEFAULT_BLOCK_ROWS, LANES, init_pallas, rng_pallas


class PrngState(NamedTuple):
    """Double-plane uint32 PRNG state for ``n`` 64-bit streams."""

    hi: jax.Array     # (rows, 128) uint32
    lo: jax.Array     # (rows, 128) uint32
    n: int            # real number of streams (rows*128 >= n)


def _layout(n: int, block_rows: int) -> int:
    """Rows of the (rows, LANES) layout covering n values — the
    ``suggest_batching`` result specialized to this kernel's quantum."""
    quantum = block_rows * LANES
    padded = ((n + quantum - 1) // quantum) * quantum
    return padded // LANES


@functools.partial(jax.jit, static_argnames=("n", "block_rows", "use_pallas"))
def _init(n: int, block_rows: int = DEFAULT_BLOCK_ROWS,
          use_pallas: bool = True) -> Tuple[jax.Array, jax.Array]:
    rows = _layout(n, block_rows)
    if use_pallas:
        return init_pallas(n, rows, block_rows, interpret=interpret_mode())
    gids = (jnp.arange(rows * LANES, dtype=jnp.uint32).reshape(rows, LANES))
    hi, lo = _ref.init_ref(gids)
    live = gids < jnp.uint32(n)
    return jnp.where(live, hi, 0), jnp.where(live, lo, 0)


def prng_init(n: int, block_rows: int = DEFAULT_BLOCK_ROWS,
              use_pallas: bool = True) -> PrngState:
    hi, lo = _init(n, block_rows, use_pallas)
    return PrngState(hi, lo, n)


@functools.partial(jax.jit, static_argnames=("block_rows", "use_pallas"))
def _step(hi: jax.Array, lo: jax.Array,
          block_rows: int = DEFAULT_BLOCK_ROWS,
          use_pallas: bool = True) -> Tuple[jax.Array, jax.Array]:
    if use_pallas:
        return rng_pallas(hi, lo, block_rows, interpret=interpret_mode())
    return _ref.rng_ref(hi, lo)


def prng_step(state: PrngState, block_rows: int = DEFAULT_BLOCK_ROWS,
              use_pallas: bool = True) -> PrngState:
    hi, lo = _step(state.hi, state.lo, block_rows, use_pallas)
    return PrngState(hi, lo, state.n)


# -- consumers -----------------------------------------------------------------

def to_uint64(state: PrngState) -> np.ndarray:
    """Flatten to the first n 64-bit values (host-side, like the paper's
    fwrite of the read buffer)."""
    hi = np.asarray(state.hi).reshape(-1)[: state.n]
    lo = np.asarray(state.lo).reshape(-1)[: state.n]
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


@jax.jit
def to_uniform(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Map the high plane to floats in [0, 1) — device-side consumer used
    by the data pipeline."""
    return hi.astype(jnp.float32) * (1.0 / 4294967296.0)


@functools.partial(jax.jit, static_argnames=("vocab",))
def to_tokens(hi: jax.Array, vocab: int) -> jax.Array:
    """Map the high plane to token IDs in [0, vocab) — synthetic LM data."""
    return (hi % jnp.uint32(vocab)).astype(jnp.int32)


__all__ = ["PrngState", "prng_init", "prng_step", "to_uint64", "to_uniform",
           "to_tokens", "LANES", "DEFAULT_BLOCK_ROWS"]
