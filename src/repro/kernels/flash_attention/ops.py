"""Public op: flash_attention with XLA fallback and autotuned routing.

``impl="pallas"`` uses the BlockSpec'd TPU kernel (interpret-mode on CPU);
``impl="xla"`` uses the jnp reference (what the dry-run lowers, since
Pallas custom-calls don't lower to the CPU placeholder backend);
``impl="auto"`` asks the autotuner (kernels/autotune.py) to resolve the
shape key to a concrete config — a measured winner if one is cached, the
deterministic cost model otherwise.  Resolution is a host-side lookup on
static shapes, so it composes with an enclosing jit.  Model code selects
via config; numerics agree to bf16 tolerance (tested).

``q_pos``/``k_pos`` ((B, T)/(B, S) or (T,)/(S,) int32) switch both impls
to explicit position planes (``-1`` = padded, masked out) — the partial
prefill and bucketed serve layouts.  ``q_offset`` sets query row 0's
absolute position in the arithmetic mode (default ``S - T``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..autotune import flash_shape_key, get_autotuner
from ..common import interpret_mode
from .flash_attention import flash_attention_pallas
from .ref import attention_pos_ref, attention_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "impl", "block_q", "block_kv", "q_offset"))
def _flash_attention(q, k, v, q_pos, k_pos, causal: bool = True,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     impl: str = "pallas",
                     block_q: int = 512, block_kv: int = 512,
                     q_offset: Optional[int] = None):
    if impl == "xla":
        if q_pos is not None:
            return attention_pos_ref(q, k, v, q_pos, k_pos, causal=causal,
                                     window=window, scale=scale)
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, q_offset=q_offset,
        q_pos=q_pos, k_pos=k_pos, interpret=interpret_mode())


def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    impl: str = "pallas",
                    block_q: int = 512, block_kv: int = 512,
                    q_offset: Optional[int] = None,
                    q_pos=None, k_pos=None):
    if q_pos is not None:
        B, _, T, _ = q.shape
        S = k.shape[2]
        q_pos = jnp.asarray(q_pos, jnp.int32)
        k_pos = jnp.asarray(k_pos, jnp.int32)
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (B, T))
        if k_pos.ndim == 1:
            k_pos = jnp.broadcast_to(k_pos[None, :], (B, S))
    if impl == "auto":
        cfg = get_autotuner().choose(flash_shape_key(q, k))
        impl = cfg.impl
        if cfg.block_q:
            block_q = cfg.block_q
        if cfg.block_kv:
            block_kv = cfg.block_kv
    return _flash_attention(q, k, v, q_pos, k_pos, causal=causal,
                            window=window, scale=scale, impl=impl,
                            block_q=block_q, block_kv=block_kv,
                            q_offset=q_offset)


__all__ = ["flash_attention"]
