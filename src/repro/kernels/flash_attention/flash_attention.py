"""Flash attention forward — Pallas TPU kernel.

TPU-native blocking (DESIGN.md §2): the kernel iterates a 4-D grid
``(batch, q_head, q_block, kv_block)`` with the kv dimension innermost and
"arbitrary" semantics, keeping running softmax statistics in VMEM scratch
(the FlashAttention online-softmax recurrence).  Block shapes are
q/o tiles (block_q, d_head), k/v tiles (block_kv, d_head); d_head is
taken whole (no padding: a block dim equal to the array's is legal, so
head_dim 64 runs as is).  ``block_q``/``block_kv`` are requests: the
wrapper takes the largest multiple of 8 dividing the axis that does not
exceed them, or the whole axis (``common.aligned_block``).

Supports causal masking, sliding-window masking (Mistral/RecurrentGemma
style) and GQA via index-map head division — one kernel serves the dense,
MoE and hybrid architectures in this repo.

Two position modes:

* **Index arithmetic** (default): query ``i`` sits at absolute position
  ``i + q_offset`` with ``q_offset = S - T`` — queries at the tail.  An
  explicit ``q_offset`` generalizes this to partial prefill: extending a
  prefix cache of length ``s`` runs ``T = L - s`` queries over ``S = L``
  keys with ``q_offset = s``, which is exactly the default — the
  start-offset form is what lets prefix-shared prefill stay on Pallas.
  Causal/window whole-block skips are static in this mode.
* **Explicit position planes** (``q_pos (B, T)``, ``k_pos (B, S)``
  int32, viewed as ``(B, T/block_q, 1, block_q)`` and
  ``(B, S/block_kv, 1, block_kv)`` so each cell's ``(1, block)`` row is a
  whole trailing tile for any batch): positions are data, for the
  bucketed serve layouts where rows are padded (``pos = -1`` masks a
  row/key out entirely) and spans are non-contiguous (prefix pad +
  tail).  No static block skip — but every
  masked contribution is an exact no-op in the online-softmax update, so
  numerics match the arithmetic mode bit-for-bit on the same
  ``(S, block_kv)`` partition.

VMEM budget at defaults (block_q=block_kv=512, d=128, bf16 in / f32 acc):
q 512·128·2 + k/v 2·512·128·2 + acc 512·128·4 + m/l 2·512·128·4 ≈ 1.2 MiB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANES, NEG_INF, aligned_block


def _flash_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                  window: Optional[int], block_q: int, block_kv: int,
                  kv_steps: int, q_offset: int, has_pos: bool):
    if has_pos:
        qp_ref, kp_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if has_pos:
        # positions are data: padded rows/keys carry -1 and mask out
        q_pos = qp_ref[0, 0].reshape(block_q, 1)
        k_pos = kp_ref[0, 0]                          # (1, block_kv)
    else:
        # absolute positions from index arithmetic (queries start at
        # q_offset; the default q_offset = S - T puts them at the tail)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0) + q_offset
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)

    masked = causal or window is not None or has_pos

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bkv)
        mask = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
        if has_pos:
            mask &= k_pos >= 0
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # (bq, LANES)
        m_cur = jnp.max(s, axis=1, keepdims=True)     # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)               # (bq, LANES)
        p = jnp.exp(s - m_new[:, :1])                 # (bq, bkv)
        if masked:
            # without a static block skip a block can be *fully* masked
            # while m is still NEG_INF; exp(NEG_INF - NEG_INF) = 1 would
            # poison l/acc, so masked entries contribute an explicit 0.
            # Wherever any valid key has been seen this is the value the
            # underflow already produced — bit-identical, never weaker.
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[...] + \
            jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True),
                             m_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal and not has_pos:
        # whole-block skip: first key of block beyond last query of block
        # (index arithmetic only — with position planes, masking is data)
        first_k = ki * block_kv
        last_q = qi * block_q + block_q - 1 + q_offset
        needed = first_k <= last_q
        if window is not None:
            # also skip blocks entirely left of every query's window
            last_k = ki * block_kv + block_kv - 1
            first_q = qi * block_q + q_offset
            needed = jnp.logical_and(needed, last_k > first_q - window)
        pl.when(needed)(_body)
    else:
        _body()

    @pl.when(ki == kv_steps - 1)
    def _final():
        l = l_scr[...][:, :1]                          # (bq, 1)
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           block_q: int = 512, block_kv: int = 512,
                           q_offset: Optional[int] = None,
                           q_pos: Optional[jax.Array] = None,
                           k_pos: Optional[jax.Array] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, T, D); k, v: (B, Hkv, S, D) → (B, Hq, T, D).

    ``q_offset`` (default ``S - T``): absolute position of query row 0 —
    pass the prefix length ``s`` for partial prefill (which the default
    already is when ``S = s + T``).  ``q_pos``/``k_pos`` ((B, T) / (B, S)
    int32, both or neither) switch to explicit position planes; ``-1``
    marks padded rows/keys (masked out, padded query rows emit zeros).
    """
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    assert (q_pos is None) == (k_pos is None), "pass both planes or neither"
    has_pos = q_pos is not None
    group = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if q_offset is None:
        q_offset = S - T
    block_q = aligned_block(T, block_q)
    block_kv = aligned_block(S, block_kv)
    q_steps, kv_steps = T // block_q, S // block_kv
    grid = (B, Hq, q_steps, kv_steps)

    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, D),
                           lambda b, h, i, j: (b, h // group, j, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, i, j: (b, h, i, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if has_pos:
        in_specs += [pl.BlockSpec((1, 1, 1, block_q),
                                  lambda b, h, i, j: (b, i, 0, 0)),
                     pl.BlockSpec((1, 1, 1, block_kv),
                                  lambda b, h, i, j: (b, j, 0, 0))]
        operands += [
            jnp.asarray(q_pos, jnp.int32).reshape(B, q_steps, 1, block_q),
            jnp.asarray(k_pos, jnp.int32).reshape(B, kv_steps, 1, block_kv)]

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, kv_steps=kv_steps,
        q_offset=q_offset, has_pos=has_pos)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=tuple(in_specs),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # m
            pltpu.VMEM((block_q, LANES), jnp.float32),   # l
            pltpu.VMEM((block_q, D), jnp.float32),       # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)


__all__ = ["flash_attention_pallas"]
