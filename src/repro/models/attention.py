"""Attention layers: GQA/MQA with RoPE, qk-norm, sliding-window, chunked-local
and cross-attention; ring-buffer KV-cache for decode.

Training/prefill attention can run through the Pallas flash kernel
(cfg.attn_impl="pallas"), the jnp path ("xla", default for dry-runs), or
the autotuned router ("auto": the kernel ops resolve each shape key to
its winning config — see kernels/autotune.py).  Decode runs through the
fused Pallas decode kernel (cache write + split-S single-query attention
in one ``pallas_call``) when ``cfg.attn_impl`` is "pallas"/"auto", with
``_xla_attention`` as the reference fallback.  Partial (prefix-shared)
prefill runs the flash kernel too, via explicit position planes — no
XLA-only fallback remains on the serving path.

Ring-buffer cache (DESIGN.md "Serving path"): ``KVCache`` carries the
absolute position of every slot alongside k/v.  Slot ``j`` of a cache of
length ``S`` holds position ``p ≡ j (mod S)`` (``pos[j] = -1`` while
unwritten); decode writes at ``pos mod S`` for *all* cache kinds and
masking is purely by stored position, so full, sliding-window and
partially-filled caches share one code path and no roll/realign copies
are ever needed.  A ``pos=None`` cache falls back to the legacy
arithmetic-position scheme (kept for direct KVCache(k, v) constructions).

Per-sequence decode (continuous batching): when ``positions`` arrives as a
``(B, T)`` plane every batch row may sit at a different absolute depth —
ring writes become per-batch scatters (``widx[b] = pos[b] mod S``) and the
fused kernel receives the ``(B,)`` position vector.  ``pos[b] = -1`` marks
an inactive serve slot: all of its keys mask out and its output is garbage
by construction (the serve engine repacks the slot's cache on admission).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import shard_activation
from ..kernels.decode_attention.ops import decode_attention
from ..kernels.flash_attention.ops import flash_attention
from . import layers as L
from .layers import ParamTpl
from .scan_util import maybe_scan


def attn_tpl(d: int, n_heads: int, n_kv: int, head_dim: int, dtype: str,
             qk_norm: bool = False) -> Dict[str, ParamTpl]:
    tpl = {
        "wq": ParamTpl((d, n_heads * head_dim), ("embed", "heads_flat"),
                       "normal", dtype),
        "wk": ParamTpl((d, n_kv * head_dim), ("embed", "kv_flat"),
                       "normal", dtype),
        "wv": ParamTpl((d, n_kv * head_dim), ("embed", "kv_flat"),
                       "normal", dtype),
        "wo": ParamTpl((n_heads * head_dim, d), ("heads_flat", "embed"),
                       "normal", dtype),
    }
    if qk_norm:
        tpl["q_norm"] = ParamTpl((head_dim,), ("state",), "ones", dtype)
        tpl["k_norm"] = ParamTpl((head_dim,), ("state",), "ones", dtype)
    return tpl


class KVCache(NamedTuple):
    k: jax.Array                     # (B, Hkv, S, Dh) dense; paged: see below
    v: jax.Array
    # absolute position stored in each ring slot, -1 = never written
    # (B, S) int32; None → legacy arithmetic positions (see module doc)
    pos: Optional[jax.Array] = None
    # paged layout (serve/paging.py): when set, k/v are page arenas
    # (n_pages, Hkv, page_size, Dh) shared by every sequence, pos is the
    # paged validity plane (n_pages, page_size), and page_table (B, n_ptes)
    # int32 maps each sequence's logical ring page t to a physical page
    # (entry 0 = reserved null page).  The ring invariant becomes
    # page-local: slot j of logical page t holds position
    # p ≡ (t·page_size + j) (mod W) with W = n_ptes·page_size.
    # None → dense per-slot rings (the layout everything else uses).
    page_table: Optional[jax.Array] = None


def _split_heads(x, n, dh):
    B, T, _ = x.shape
    return x.reshape(B, T, n, dh).transpose(0, 2, 1, 3)


def _merge_heads(x):
    B, H, T, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


def _qk_norm(q, w, eps=1e-6):
    qf = q.astype(jnp.float32)
    var = jnp.mean(qf * qf, axis=-1, keepdims=True)
    return (qf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            ).astype(q.dtype)


def self_attention(p, x, cfg, kind: str, positions,
                   cache: Optional[KVCache] = None,
                   rolling: bool = False
                   ) -> Tuple[jax.Array, Optional[KVCache]]:
    """kind ∈ {full, swa, local, chunked, global_nope}."""
    B, T, D = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(x @ p["wq"], H, Dh)
    k = _split_heads(x @ p["wk"], Hkv, Dh)
    v = _split_heads(x @ p["wv"], Hkv, Dh)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.rms_eps)
        k = _qk_norm(k, p["k_norm"], cfg.rms_eps)
    use_rope = kind != "global_nope"
    if use_rope:
        q = L.rope(q, positions, cfg.rope_for(kind))
        k = L.rope(k, positions, cfg.rope_for(kind))
    q = shard_activation(q, ("batch", "heads", None, None))
    k = shard_activation(k, ("batch", "kv_heads", None, None))

    window = None
    if kind in ("swa", "local"):
        window = cfg.window
    elif kind == "chunked":
        window = cfg.chunk   # approximation of chunked-local masking

    if cache is None:
        # training/prefill: self-contained sequence
        if cfg.attn_impl in ("pallas", "auto"):
            # explicit position planes: bucketed prefill pads rows with
            # pos = -1, which must mask (identical reductions to the
            # index-arithmetic mode on un-padded layouts)
            out = flash_attention(q, k, v, causal=True, window=window,
                                  impl=cfg.attn_impl,
                                  q_pos=positions.astype(jnp.int32),
                                  k_pos=positions.astype(jnp.int32))
        elif T > 1024:
            # chunked online-softmax (flash semantics in pure XLA) — never
            # materializes the (T, S) score matrix; required for the 32k
            # prefill shapes and it is also the memory-friendly train path
            out = _xla_flash(q, k, v, causal=True, window=window,
                             q_pos=positions, k_pos=positions,
                             chunk=cfg.attn_chunk,
                             unroll=cfg.analysis_unroll,
                             qblocks=cfg.attn_qblocks)
        else:
            out = _xla_attention(q, k, v, causal=True, window=window,
                                 q_pos=positions, k_pos=positions)
        # prefill mode: the post-RoPE K and V *are* the decode cache;
        # slot j of the collected cache holds absolute position j
        cdt = jnp.dtype(cfg.dtype)
        new_cache = None
        if cfg.collect_kv:
            cache_pos = jnp.broadcast_to(
                positions.astype(jnp.int32)[None, :], (B, T))
            new_cache = KVCache(k.astype(cdt), v.astype(cdt), cache_pos)
    elif cfg.collect_kv:
        # partial prefill (prefix sharing): extend a dense
        # position-carrying *prefix* cache of length s — keys are the
        # prefix K/V (bit-exact pages gathered back from the paged pool)
        # concatenated with this call's fresh K/V, and the collected
        # cache covers the full [0, s+T) span, so everything downstream
        # (ring alignment, page donation) is oblivious to the split.
        # Row-for-row this matches the one-shot prefill: each output row
        # is the same masked reduction over the same s+T keys, merely
        # computed with a shorter query block.
        assert cache.pos is not None and cache.page_table is None, \
            "partial prefill extends a dense position-carrying prefix"
        assert positions.ndim == 1, \
            "partial prefill takes contiguous scalar-offset positions"
        kf = jnp.concatenate([cache.k.astype(k.dtype), k], axis=2)
        vf = jnp.concatenate([cache.v.astype(v.dtype), v], axis=2)
        kp = jnp.concatenate(
            [cache.pos.astype(jnp.int32),
             jnp.broadcast_to(positions.astype(jnp.int32)[None, :],
                              (B, T))], axis=1)
        if cfg.attn_impl in ("pallas", "auto"):
            # flash kernel with explicit position planes: the tail's T
            # queries reduce over the same s+T keys, in the same
            # block_kv partition, as the one-shot prefill — so partial
            # prefill is row-for-row bit-exact against it (tested) and
            # prefix sharing stays enabled under Pallas prefill
            out = flash_attention(q, kf, vf, causal=True, window=window,
                                  impl=cfg.attn_impl,
                                  q_pos=positions.astype(jnp.int32),
                                  k_pos=kp)
        elif kf.shape[2] > 1024:
            # mirror the one-shot prefill's flash threshold so a long
            # shared prefill and its unshared twin take the same
            # numerical path
            assert B == 1, "partial prefill is batch=1 (admission)"
            out = _xla_flash(q, kf, vf, causal=True, window=window,
                             q_pos=positions, k_pos=kp[0],
                             chunk=cfg.attn_chunk,
                             unroll=cfg.analysis_unroll,
                             qblocks=cfg.attn_qblocks)
        else:
            out = _xla_attention(q, kf, vf, causal=True, window=window,
                                 q_pos=positions, k_pos=kp)
        cdt = jnp.dtype(cfg.dtype)
        new_cache = KVCache(kf.astype(cdt), vf.astype(cdt), kp)
    elif positions.ndim == 2:
        # decode, per-sequence positions (B, T): every sequence sits at its
        # own depth (continuous batching).  Ring writes are per-batch
        # scatters at widx[b] = pos[b] mod S; requires position-carrying
        # caches (the legacy arithmetic scheme cannot express mixed depths).
        assert T == 1, "per-sequence decode is single-token"
        assert cache.pos is not None, \
            "per-sequence decode needs a position-carrying cache"
        pos_b = positions[:, 0].astype(jnp.int32)          # (B,)
        # one op serves both impls: the fused kernel or its jnp oracle —
        # per-row ring-write + position-masking semantics live in exactly
        # one place (kernels/decode_attention).  A paged cache routes its
        # page table through so the ring gather/write go via the pool.
        out, ck, cv, cpos = decode_attention(
            q, cache.k, cache.v, cache.pos, k.astype(cache.k.dtype),
            v.astype(cache.v.dtype), pos_b, window=window,
            impl=cfg.attn_impl, page_table=cache.page_table)
        new_cache = KVCache(ck, cv, cpos, cache.page_table)
    else:
        # decode: write k/v into the ring slot, attend over the cache
        assert cache.page_table is None, \
            "paged caches decode through the per-sequence (B, T) path"
        S = cache.k.shape[2]
        pos = positions if positions.ndim == 0 else positions.reshape(-1)[0]
        if cache.pos is not None and T == 1 and \
                cfg.attn_impl in ("pallas", "auto"):
            # fused path: cache write + split-S attention in one kernel
            out, ck, cv, cpos = decode_attention(
                q, cache.k, cache.v, cache.pos, k.astype(cache.k.dtype),
                v.astype(cache.v.dtype), pos, window=window,
                impl=cfg.attn_impl)
            new_cache = KVCache(ck, cv, cpos)
        else:
            widx = jnp.mod(pos, S) if (rolling or cache.pos is not None) \
                else pos
            # indices share one dtype (x64 would promote the literal 0s)
            widx = jnp.asarray(widx, jnp.int32)
            z = jnp.zeros((), jnp.int32)
            ck = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (z, z, widx, z))
            cv = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (z, z, widx, z))
            if cache.pos is not None:
                cpos = jax.lax.dynamic_update_slice(
                    cache.pos, jnp.full((B, 1), pos, cache.pos.dtype),
                    (z, widx))
                new_cache = KVCache(ck, cv, cpos)
                k_pos = cpos
            else:
                # legacy layout: positions derived from slot arithmetic
                new_cache = KVCache(ck, cv)
                if rolling:
                    k_pos = pos - jnp.mod(pos - jnp.arange(S), S)
                else:
                    k_pos = jnp.arange(S)
            q_pos = jnp.full((T,), pos)
            out = _xla_attention(q, ck, cv, causal=True, window=window,
                                 q_pos=q_pos, k_pos=k_pos)
    out = _merge_heads(out.astype(x.dtype))
    return out @ p["wo"], new_cache


def _xla_attention(q, k, v, causal: bool, window: Optional[int],
                   q_pos, k_pos):
    """jnp attention with explicit positions (supports rolling caches)."""
    B, H, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = H // Hkv
    scale = Dh ** -0.5
    if q_pos.ndim == 1:
        q_pos = q_pos[None, :]
    if k_pos.ndim == 1:
        k_pos = k_pos[None, :]
    qh = q.reshape(B, Hkv, group, T, Dh)
    logits = jnp.einsum("bhgtd,bhsd->bhgts", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    # k_pos < 0 marks not-yet-written rolling-buffer slots (pos-j wraps
    # below zero before the buffer fills) — always invalid
    mask = k_pos[:, None, :] >= 0
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bhgtd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, T, Dh)


import functools


def _xla_flash(q, k, v, causal: bool, window: Optional[int], q_pos, k_pos,
               chunk: int = 1024, unroll: bool = False, qblocks: int = 1):
    """Online-softmax attention, scanning KV chunks — bounded memory with a
    flash-style custom VJP (only O(T) softmax stats are saved; the backward
    pass re-streams KV chunks).  The XLA analogue of the Pallas kernel.

    ``qblocks > 1`` (§Perf lever): split queries into blocks and, under a
    causal/windowed mask with contiguous positions, statically skip KV
    chunks that are fully masked for the block — ~(Q+1)/2Q of the full
    causal compute.  Baseline (qblocks=1) computes every chunk masked.
    ``unroll`` = analysis mode (scan_util).
    """
    if q_pos.ndim == 1:
        q_pos = q_pos[None, :]
    return _flash_core(causal, window, chunk, unroll, qblocks, q, k, v,
                       q_pos.astype(jnp.float32),
                       k_pos.astype(jnp.float32))


def _chunk_mask(causal, window, B, T, ck, qp, kpi):
    msk = jnp.ones((B, T, ck), bool)
    if causal:
        msk &= kpi[None, None, :] <= qp[:, :, None]
    if window is not None:
        msk &= kpi[None, None, :] > qp[:, :, None] - window
    return msk


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_core(causal, window, chunk, unroll, qblocks, q, k, v, q_pos,
                k_pos):
    out, _ = _flash_fwd_impl(causal, window, chunk, unroll, qblocks, q, k, v,
                             q_pos, k_pos)
    return out


def _normalize_chunk(chunk, S):
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    return chunk


def _chunk_range(causal, window, chunk, nc, qb_start, qb_end, off):
    """Static KV-chunk range needed by queries [qb_start, qb_end) assuming
    contiguous positions (pos = index + off).  Full range if not causal."""
    if not causal:
        return 0, nc
    last_q = qb_end - 1 + off
    hi = min(nc, last_q // chunk + 1)
    lo = 0
    if window is not None:
        first_q = qb_start + off
        lo = max(0, (first_q - window + 1) // chunk)
    return lo, hi


def _flash_fwd_impl(causal, window, chunk, unroll, qblocks, q, k, v,
                    q_pos, k_pos):
    B, H, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = H // Hkv
    scale = Dh ** -0.5
    chunk = _normalize_chunk(chunk, S)
    nc = S // chunk
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, T, Dh)
    kc = k.reshape(B, Hkv, nc, chunk, Dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nc, chunk, Dh).transpose(2, 0, 1, 3, 4)
    kp = k_pos.reshape(nc, chunk)
    qblocks = qblocks if (T % qblocks == 0 and causal) else 1
    Tb = T // qblocks
    off = S - T

    outs, lses = [], []
    for qi in range(qblocks):
        qfb = qf[..., qi * Tb:(qi + 1) * Tb, :]
        qpb = q_pos[:, qi * Tb:(qi + 1) * Tb]
        lo, hi = _chunk_range(causal, window, chunk, nc,
                              qi * Tb, (qi + 1) * Tb, off)

        def body(carry, inp, qfb=qfb, qpb=qpb):
            m, l, acc = carry
            kci, vci, kpi = inp
            s = jnp.einsum("bhgtd,bhsd->bhgts", qfb,
                           kci.astype(jnp.float32)) * scale
            msk = _chunk_mask(causal, window, B, Tb, chunk, qpb, kpi)
            s = jnp.where(msk[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgts,bhsd->bhgtd", p, vci.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, group, Tb), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, group, Tb), jnp.float32)
        acc0 = jnp.zeros((B, Hkv, group, Tb, Dh), jnp.float32)
        (m, l, acc), _ = maybe_scan(
            body, (m0, l0, acc0),
            (kc[lo:hi], vc[lo:hi], kp[lo:hi]), unroll=unroll)
        l = jnp.maximum(l, 1e-30)
        outs.append((acc / l[..., None]).astype(q.dtype))
        lses.append(m + jnp.log(l))
    out = jnp.concatenate(outs, axis=3).reshape(B, H, T, Dh) \
        if qblocks > 1 else outs[0].reshape(B, H, T, Dh)
    lse = jnp.concatenate(lses, axis=3) if qblocks > 1 else lses[0]
    return out, lse


def _flash_fwd(causal, window, chunk, unroll, qblocks, q, k, v, q_pos,
               k_pos):
    out, lse = _flash_fwd_impl(causal, window, chunk, unroll, qblocks, q, k,
                               v, q_pos, k_pos)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _flash_bwd(causal, window, chunk, unroll, qblocks, res, dout):
    q, k, v, q_pos, k_pos, out, lse = res
    B, H, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = H // Hkv
    scale = Dh ** -0.5
    chunk = _normalize_chunk(chunk, S)
    nc = S // chunk
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, T, Dh)
    dof = dout.astype(jnp.float32).reshape(B, Hkv, group, T, Dh)
    of = out.astype(jnp.float32).reshape(B, Hkv, group, T, Dh)
    Dvec = jnp.sum(dof * of, axis=-1)          # (B,Hkv,g,T)
    kc = k.reshape(B, Hkv, nc, chunk, Dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nc, chunk, Dh).transpose(2, 0, 1, 3, 4)
    kp = k_pos.reshape(nc, chunk)
    qblocks = qblocks if (T % qblocks == 0 and causal) else 1
    Tb = T // qblocks
    off = S - T

    dqs = []
    dk = jnp.zeros((nc, B, Hkv, chunk, Dh), jnp.float32)
    dv = jnp.zeros((nc, B, Hkv, chunk, Dh), jnp.float32)
    for qi in range(qblocks):
        sl = slice(qi * Tb, (qi + 1) * Tb)
        qfb, dofb = qf[..., sl, :], dof[..., sl, :]
        qpb, lseb, Dvb = q_pos[:, sl], lse[..., sl], Dvec[..., sl]
        lo, hi = _chunk_range(causal, window, chunk, nc,
                              qi * Tb, (qi + 1) * Tb, off)

        def body(dq, inp, qfb=qfb, dofb=dofb, qpb=qpb, lseb=lseb, Dvb=Dvb):
            kci, vci, kpi = inp
            kcf, vcf = kci.astype(jnp.float32), vci.astype(jnp.float32)
            s = jnp.einsum("bhgtd,bhsd->bhgts", qfb, kcf) * scale
            msk = _chunk_mask(causal, window, B, Tb, chunk, qpb, kpi)
            s = jnp.where(msk[:, None, None], s, -1e30)
            p = jnp.exp(s - lseb[..., None])
            dv_c = jnp.einsum("bhgts,bhgtd->bhsd", p, dofb)
            dp = jnp.einsum("bhgtd,bhsd->bhgts", dofb, vcf)
            ds = p * (dp - Dvb[..., None])
            dq = dq + jnp.einsum("bhgts,bhsd->bhgtd", ds, kcf) * scale
            dk_c = jnp.einsum("bhgts,bhgtd->bhsd", ds, qfb) * scale
            return dq, (dk_c, dv_c)

        dq0 = jnp.zeros((B, Hkv, group, Tb, Dh), jnp.float32)
        dq, (dk_c, dv_c) = maybe_scan(
            body, dq0, (kc[lo:hi], vc[lo:hi], kp[lo:hi]), unroll=unroll)
        dqs.append(dq)
        dk = dk.at[lo:hi].add(dk_c)
        dv = dv.at[lo:hi].add(dv_c)
    dq = (jnp.concatenate(dqs, axis=3) if qblocks > 1 else dqs[0]
          ).reshape(B, H, T, Dh).astype(q.dtype)
    dk = dk.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, S, Dh).astype(k.dtype)
    dv = dv.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, S, Dh).astype(v.dtype)
    zq = jnp.zeros_like(q_pos)
    zk = jnp.zeros_like(k_pos)
    return dq, dk, dv, zq, zk


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------- cross ------

def cross_attn_tpl(d: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype: str) -> Dict[str, ParamTpl]:
    return attn_tpl(d, n_heads, n_kv, head_dim, dtype)


def cross_attention(p, x, ctx_kv: Tuple[jax.Array, jax.Array], cfg
                    ) -> jax.Array:
    """Cross-attention to precomputed (k, v) of the context (encoder output
    or vision tokens).  ctx k/v: (B, Hkv, S_ctx, Dh)."""
    B, T, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    q = _split_heads(x @ p["wq"], H, Dh)
    k, v = ctx_kv
    S = k.shape[2]
    out = _xla_attention(q, k, v, causal=False, window=None,
                         q_pos=jnp.zeros((T,), jnp.int32),
                         k_pos=jnp.zeros((S,), jnp.int32))
    return _merge_heads(out.astype(x.dtype)) @ p["wo"]


def context_kv(p, ctx: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from context embeddings."""
    k = _split_heads(ctx @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(ctx @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    return k, v


def bidir_attention(p, x, cfg) -> jax.Array:
    """Bidirectional self-attention (whisper encoder)."""
    B, T, D = x.shape
    q = _split_heads(x @ p["wq"], cfg.n_heads, cfg.head_dim)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cfg.attn_impl in ("pallas", "auto"):
        out = flash_attention(q, k, v, causal=False, impl=cfg.attn_impl)
    else:
        pos = jnp.arange(T)
        out = _xla_attention(q, k, v, causal=False, window=None,
                             q_pos=pos, k_pos=pos)
    return _merge_heads(out.astype(x.dtype)) @ p["wo"]


__all__ = ["attn_tpl", "cross_attn_tpl", "self_attention", "cross_attention",
           "context_kv", "bidir_attention", "KVCache"]
