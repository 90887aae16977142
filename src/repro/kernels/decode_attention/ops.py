"""Public op: fused decode attention + ring-cache write, with XLA fallback.

``impl="pallas"`` runs the flash-decode split-S kernel (interpret-mode on
CPU); ``impl="xla"`` runs the jnp reference — identical semantics, used by
dry-runs and as the correctness oracle; ``impl="auto"`` resolves the call's
shape key through the autotuner (kernels/autotune.py): a measured winner
from the on-disk cache if one exists, the deterministic cost model
otherwise.  Resolution reads only static shapes, so it runs at trace time
under an enclosing jit.  Both kernel paths return the updated cache
tensors so the caller's KVCache pytree is rebuilt functionally; under jit
on TPU the pallas path updates the cache in place (input/output aliasing).

The position array is updated *before* the kernel call (a per-row scatter
into the (B, S) int32 plane — negligible next to the cache traffic) so
masking inside the kernel sees the new token as valid and the evicted
slot's old position is gone.  ``pos`` may be a scalar (lockstep batch) or
a ``(B,)`` vector (continuous batching: every sequence at its own decode
depth; the ring write index is per-sequence, ``widx[b] = pos[b] mod S``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..autotune import decode_shape_key, get_autotuner
from ..common import interpret_mode
from .decode_attention import (decode_attention_paged_pallas,
                               decode_attention_pallas)
from .ref import decode_attention_paged_ref, decode_attention_ref


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "impl", "block_kv"))
def _decode_attention(q, k_cache, v_cache, pos_cache, k_new, v_new, pos,
                      window: Optional[int] = None,
                      scale: Optional[float] = None,
                      impl: str = "pallas",
                      block_kv: int = 256,
                      page_table=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    if page_table is not None:
        return _decode_attention_paged(q, k_cache, v_cache, pos_cache,
                                       k_new, v_new, pos, page_table,
                                       window, scale, impl)
    if impl == "xla":
        return decode_attention_ref(q, k_cache, v_cache, pos_cache,
                                    k_new, v_new, pos, window=window,
                                    scale=scale)
    S = k_cache.shape[2]
    B = pos_cache.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    # scalar pos = lockstep batch; (B,) pos = per-sequence decode depths
    pos = jnp.broadcast_to(pos.reshape(-1) if pos.ndim else pos, (B,))
    widx = jnp.mod(pos, S)
    new_pos = pos_cache.at[jnp.arange(B), widx].set(
        pos.astype(pos_cache.dtype))
    out, ok, ov = decode_attention_pallas(
        q, k_cache, v_cache, new_pos, k_new, v_new, widx, pos,
        window=window, scale=scale, block_kv=block_kv,
        interpret=interpret_mode())
    return out, ok, ov, new_pos


def decode_attention(q, k_cache, v_cache, pos_cache, k_new, v_new, pos,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     impl: str = "pallas",
                     block_kv: int = 256,
                     page_table=None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused decode step; see ``ref.decode_attention_ref`` for shapes.

    ``pos`` may be a scalar (lockstep batch) or ``(B,)`` (per-sequence
    decode depths, the continuous-batching case).  With ``page_table``
    ((B, n_ptes) int32), the caches are the paged-pool arenas
    ((n_pages, Hkv, page_size, D) K/V, (n_pages, page_size) positions) and
    the step's ring write/read are routed through the table — see
    ``ref.decode_attention_paged_ref``.  Rows of the table may alias the
    same physical page (prefix sharing): aliased *reads* are unchanged by
    design — the gather is pure indirection — but the caller must
    guarantee no two rows *write* the same physical page in one step, and
    that a written page is referenced by exactly one row (the pool's
    copy-on-write invariant: a page is writable iff its refcount is 1).
    Returns ``(out, new_k_cache, new_v_cache, new_pos_cache)``.
    """
    if impl == "auto":
        cfg = get_autotuner().choose(
            decode_shape_key(q, k_cache, page_table))
        impl = cfg.impl
        if cfg.block_kv:
            block_kv = cfg.block_kv
    return _decode_attention(q, k_cache, v_cache, pos_cache, k_new, v_new,
                             pos, window=window, scale=scale, impl=impl,
                             block_kv=block_kv, page_table=page_table)


def _decode_attention_paged(q, k_arena, v_arena, pos_arena, k_new, v_new,
                            pos, page_table, window, scale, impl):
    if impl == "xla":
        return decode_attention_paged_ref(q, k_arena, v_arena, pos_arena,
                                          k_new, v_new, pos, page_table,
                                          window=window, scale=scale)
    ps = k_arena.shape[2]
    B, n_ptes = page_table.shape
    W = n_ptes * ps
    pos = jnp.asarray(pos, jnp.int32)
    pos = jnp.broadcast_to(pos.reshape(-1) if pos.ndim else pos, (B,))
    widx = jnp.mod(pos, W)
    # pre-kernel position scatter, as in the dense path — but through the
    # table: the write slot's physical page is page_table[b, widx // ps]
    ppage = page_table[jnp.arange(B), widx // ps]
    new_pos = pos_arena.at[ppage, widx % ps].set(pos.astype(pos_arena.dtype))
    out, ok, ov = decode_attention_paged_pallas(
        q, k_arena, v_arena, new_pos, k_new, v_new, page_table, widx, pos,
        window=window, scale=scale, interpret=interpret_mode())
    return out, ok, ov, new_pos


__all__ = ["decode_attention"]
