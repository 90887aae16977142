"""Public op: rmsnorm with XLA fallback (same contract as flash_attention)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import interpret_mode
from .ref import rmsnorm_ref
from .rmsnorm import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("eps", "plus_one", "impl",
                                             "block_rows"))
def rmsnorm(x, w, eps: float = 1e-6, plus_one: bool = False,
            impl: str = "pallas", block_rows: int = 256):
    if impl == "xla":
        return rmsnorm_ref(x, w, eps=eps, plus_one=plus_one)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = rmsnorm_pallas(x2, w, eps=eps, plus_one=plus_one,
                       block_rows=block_rows, interpret=interpret_mode())
    return y.reshape(shape)


__all__ = ["rmsnorm"]
