"""Fused decode-attention kernel (interpret mode) vs the jnp reference and
the model-layer ``_xla_attention`` oracle: full / rolling-window / GQA /
partially-filled ring caches, bf16 and f32, plus multi-step ring-wrap
consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.models.attention import _xla_attention

KEY = jax.random.PRNGKey(3)


def mk(B, Hq, Hkv, S, D, dtype=jnp.float32):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, Hq, 1, D), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    vc = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    kn = jax.random.normal(ks[3], (B, Hkv, 1, D), dtype)
    vn = jax.random.normal(ks[4], (B, Hkv, 1, D), dtype)
    return q, kc, vc, kn, vn


def ring_pos(B, S, pos):
    """Position plane of a ring that has seen writes 0..pos-1."""
    j = jnp.arange(S)
    if pos == 0:
        return jnp.full((B, S), -1, jnp.int32)
    newest = pos - 1
    p = newest - jnp.mod(newest - j, S)          # slot j ≡ p (mod S)
    return jnp.broadcast_to(jnp.where(p >= 0, p, -1)[None], (B, S)
                            ).astype(jnp.int32)


SWEEP = [
    # B, Hq, Hkv, S, D, window, fill, block_kv
    (2, 4, 4, 32, 16, None, 32, 8),     # full cache, MHA, split-S
    (2, 4, 2, 32, 16, None, 12, 8),     # GQA, partially filled
    (1, 8, 2, 16, 16, 16, 40, 8),       # rolling window, wrapped ring
    (2, 4, 1, 24, 32, None, 5, 256),    # MQA, odd S, single split
    (1, 4, 2, 64, 16, 32, 100, 16),     # window narrower than ring
]


@pytest.mark.parametrize("case", SWEEP)
def test_pallas_matches_ref(case):
    B, Hq, Hkv, S, D, window, fill, bkv = case
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D)
    pc = ring_pos(B, S, fill)
    pos = jnp.int32(fill)
    got = decode_attention(q, kc, vc, pc, kn, vn, pos, window=window,
                           impl="pallas", block_kv=bkv)
    want = decode_attention_ref(q, kc, vc, pc, kn, vn, pos, window=window)
    for g, w, name in zip(got, want, ["out", "k", "v", "pos"]):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_dtype_sweep(dtype, tol):
    B, Hq, Hkv, S, D = 2, 4, 2, 32, 16
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D, dtype)
    pc = ring_pos(B, S, 20)
    got, *_ = decode_attention(q, kc, vc, pc, kn, vn, jnp.int32(20),
                               impl="pallas", block_kv=8)
    want, *_ = decode_attention_ref(q, kc, vc, pc, kn, vn, jnp.int32(20))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_matches_model_xla_attention():
    """The fused op must agree with the model-layer jnp decode path
    (write via dynamic_update_slice + ``_xla_attention`` over stored
    positions)."""
    B, Hq, Hkv, S, D = 2, 8, 2, 32, 16
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D)
    for fill, window in [(10, None), (40, 16), (32, None)]:
        pc = ring_pos(B, S, fill)
        pos = jnp.int32(fill)
        widx = jnp.mod(pos, S)
        out_f, ck_f, cv_f, cp_f = decode_attention(
            q, kc, vc, pc, kn, vn, pos, window=window, impl="pallas",
            block_kv=8)
        ck = jax.lax.dynamic_update_slice(kc, kn, (0, 0, int(widx), 0))
        cv = jax.lax.dynamic_update_slice(vc, vn, (0, 0, int(widx), 0))
        cp = pc.at[:, int(widx)].set(int(pos))
        out_x = _xla_attention(q, ck, cv, causal=True, window=window,
                               q_pos=jnp.full((1,), pos), k_pos=cp)
        np.testing.assert_allclose(np.asarray(out_f, np.float32),
                                   np.asarray(out_x, np.float32),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"fill={fill} window={window}")
        np.testing.assert_allclose(np.asarray(ck_f), np.asarray(ck))
        np.testing.assert_allclose(np.asarray(cp_f), np.asarray(cp))


def test_per_sequence_pos_matches_ref():
    """(B,) position vectors (continuous batching): each batch row writes
    its own ring slot and masks at its own depth; pos = -1 marks an
    inactive slot (all keys masked, cache write lands as invalid)."""
    B, Hq, Hkv, S, D = 4, 4, 2, 32, 16
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D)
    fills = [5, 20, 40, -1]              # mixed depths + inactive slot
    pc = jnp.concatenate([ring_pos(1, S, max(f, 0)) for f in fills])
    pos = jnp.asarray(fills, jnp.int32)
    for window in [None, 16]:
        got = decode_attention(q, kc, vc, pc, kn, vn, pos, window=window,
                               impl="pallas", block_kv=8)
        want = decode_attention_ref(q, kc, vc, pc, kn, vn, pos,
                                    window=window)
        active = np.asarray(fills) >= 0
        for g, w, name in zip(got, want, ["out", "k", "v", "pos"]):
            ga = np.asarray(g, np.float32)
            wa = np.asarray(w, np.float32)
            if name == "out":            # inactive rows are garbage by
                ga, wa = ga[active], wa[active]   # construction
            np.testing.assert_allclose(ga, wa, atol=1e-5, rtol=1e-5,
                                       err_msg=f"{name} window={window}")


def test_per_sequence_ref_matches_per_row_scalar():
    """The vectorized reference must equal running each batch row alone
    through the scalar-pos reference — per-sequence semantics are exactly
    'every row is its own lockstep batch of one'."""
    B, Hq, Hkv, S, D = 3, 4, 2, 16, 16
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D)
    fills = [3, 16, 25]
    pc = jnp.concatenate([ring_pos(1, S, f) for f in fills])
    out, ck, cv, cp = decode_attention_ref(
        q, kc, vc, pc, kn, vn, jnp.asarray(fills, jnp.int32), window=8)
    for b, f in enumerate(fills):
        o1, k1, v1, p1 = decode_attention_ref(
            q[b:b + 1], kc[b:b + 1], vc[b:b + 1], pc[b:b + 1],
            kn[b:b + 1], vn[b:b + 1], jnp.int32(f), window=8)
        for g, w, name in zip([o1, k1, v1, p1],
                              [out[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                               cp[b:b + 1]], ["out", "k", "v", "pos"]):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       atol=1e-6, rtol=1e-6,
                                       err_msg=f"row {b} {name}")


def test_padded_slots_are_masked_both_impls():
    """`align_prefill_cache`'s target_len padding contract at the kernel
    level: slots carrying pos = -1 must not contribute to attention in
    either impl — a cache padded with -1 slots attends identically to the
    same keys in an unpadded cache."""
    B, Hq, Hkv, S, D = 2, 4, 2, 16, 16
    q, kc, vc, kn, vn = mk(B, Hq, Hkv, S, D)
    fill = 8
    pc = ring_pos(B, S, fill)
    # oracle: plain attention over exactly the valid keys (prefix + new)
    ck_full = jnp.concatenate([kc[:, :, :fill], kn], axis=2)
    cv_full = jnp.concatenate([vc[:, :, :fill], vn], axis=2)
    out_ref = _xla_attention(q, ck_full, cv_full, causal=True, window=None,
                             q_pos=jnp.full((1,), fill),
                             k_pos=jnp.arange(fill + 1))
    # poison the padded region: if masking ever read it, outputs move
    kc_p = kc.at[:, :, fill + 1:].set(1e3)
    vc_p = vc.at[:, :, fill + 1:].set(-1e3)
    for impl in ["xla", "pallas"]:
        out, _, _, cp = decode_attention(
            q, kc_p, vc_p, pc, kn, vn, jnp.int32(fill), impl=impl,
            block_kv=8)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out_ref, np.float32),
                                   atol=1e-5, rtol=1e-5, err_msg=impl)
        # untouched padded slots still carry -1
        np.testing.assert_array_equal(np.asarray(cp[:, fill + 1:]),
                                      -np.ones((B, S - fill - 1), np.int32))


def test_multistep_ring_wrap_consistency():
    """Decoding 3×S steps through the fused op must keep matching the
    reference step-for-step as the ring wraps repeatedly."""
    B, Hq, Hkv, S, D = 1, 4, 2, 8, 16
    ks = jax.random.split(KEY, 2 + 3 * 8)
    kc_p = vc_p = None
    kc = jnp.zeros((B, Hkv, S, D), jnp.float32)
    vc = jnp.zeros_like(kc)
    pc = jnp.full((B, S), -1, jnp.int32)
    kc_p, vc_p, pc_p = kc, vc, pc
    for t in range(3 * S):
        kq = jax.random.split(ks[t], 3)
        q = jax.random.normal(kq[0], (B, Hq, 1, D), jnp.float32)
        kn = jax.random.normal(kq[1], (B, Hkv, 1, D), jnp.float32)
        vn = jax.random.normal(kq[2], (B, Hkv, 1, D), jnp.float32)
        o_p, kc_p, vc_p, pc_p = decode_attention(
            q, kc_p, vc_p, pc_p, kn, vn, jnp.int32(t), window=S,
            impl="pallas", block_kv=4)
        o_r, kc, vc, pc = decode_attention_ref(
            q, kc, vc, pc, kn, vn, jnp.int32(t), window=S)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"step {t}")
    np.testing.assert_array_equal(np.asarray(pc_p), np.asarray(pc))


@pytest.mark.parametrize("n,want,block", [
    (37, 256, 37),        # fits: the whole axis
    (2048, 256, 256),
    (2048, 100, 64),      # largest multiple of 8 ≤ 100 dividing 2048
    (1000, 256, 200),
    (300, 256, 300),      # no multiple of 8 divides 300: the whole axis
    (8, 4, 8),
])
def test_aligned_block(n, want, block):
    """Block choice the chip's tiling accepts: a multiple of 8 dividing
    the axis, or the whole axis — never an odd divisor like 37 of 74."""
    from repro.kernels.common import aligned_block
    got = aligned_block(n, want)
    assert got == block
    assert n % got == 0 and (got % 8 == 0 or got == n)
