"""Fused decode attention — single-query flash-decode over a ring KV cache.

Serving path
------------
This kernel is the decode half of the serving hot path: one token per
sequence per step, attending over a standing KV cache that may be orders of
magnitude longer than the query.  The naive XLA formulation materializes a
``(B, H, S)`` score matrix and re-writes the cache with two
``dynamic_update_slice`` ops per layer; at production cache lengths that is
memory-bound *and* leaves all but one core idle.  Here a single
``pallas_call`` per layer:

1. **writes** the step's K/V row into the cache at slot ``pos mod S``
   (ring-buffer layout; the cache outputs alias the inputs so the update is
   in place on TPU),
2. **attends** the query over the *updated* cache with an online softmax,
   GQA head-grouping (all ``H/Hkv`` query heads of a KV head share one
   grid cell) and position-validity masking, and
3. **splits the KV axis across the grid** flash-decode style: each of the
   ``S / block_kv`` grid cells produces a partial ``(acc, m, l)`` triple
   and a cheap cross-block combine in XLA merges them — long caches use
   every core instead of one sequential lane.  The paged pool's kernel
   instead walks each row's live pages in order, all KV heads per page
   (``decode_attention.py``).

Ring-buffer invariant (see DESIGN.md): slot ``j`` of a cache of length
``S`` holds the K/V of absolute position ``p ≡ j (mod S)``, and the
``pos`` array stored alongside k/v holds that absolute position (``-1`` =
slot never written).  Masking is *only* by stored absolute position, so
partially-filled and wrapped caches need no layout fix-ups.

Layout follows the other kernel packages: ``decode_attention.py`` holds the
``pl.pallas_call`` kernel, ``ops.py`` the jitted public op with the XLA
fallback, ``ref.py`` the pure-jnp oracle.
"""

from .ops import decode_attention

__all__ = ["decode_attention"]
