"""Prefill→decode must reproduce teacher-forced logits: the strongest
end-to-end correctness check of the cache machinery (KV, rolling SWA
buffers, SSM/RG-LRU states, cross-attention contexts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve.step import align_prefill_cache, make_decode_step, \
    make_prefill_step

KEY = jax.random.PRNGKey(11)

# one dense, one swa+moe, one ssm, one hybrid, one cross-attn
CASES = ["llama3-8b", "mixtral-8x7b", "mamba2-1.3b", "recurrentgemma-9b",
         "llama-3.2-vision-11b"]


@pytest.mark.parametrize("arch", CASES)
def test_decode_matches_teacher_forcing(arch):
    import dataclasses
    cfg = get_smoke_config(arch)
    B, T = 2, 24
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
    ce = None
    if cfg.vis_tokens:
        ce = jax.random.normal(KEY, (B, cfg.vis_tokens, cfg.d_model),
                               jnp.float32)

    # teacher-forced logits over the whole sequence
    hidden, _, _ = M.forward(cfg, params, toks, ctx_embed=ce)
    tf_logits = M.logits_fn(cfg, params, hidden)

    # prefill on the first Tp tokens, then decode the rest one by one
    Tp = 16
    prefill = make_prefill_step(cfg)
    logits_p, cache = prefill(params, toks[:, :Tp], ce) if ce is not None \
        else prefill(params, toks[:, :Tp])
    cache = align_prefill_cache(cfg, cache, Tp, target_len=T)
    np.testing.assert_allclose(np.asarray(logits_p[:, -1]),
                               np.asarray(tf_logits[:, Tp - 1]),
                               atol=2e-2, rtol=2e-2)

    decode = make_decode_step(cfg)
    for t in range(Tp, T):
        logits_d, cache, _ = decode(params, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(tf_logits[:, t]),
            atol=2e-2, rtol=2e-2,
            err_msg=f"{arch}: decode diverges at position {t}")


# dense full-cache + SWA ring cache: the two layouts the fused kernel serves
PALLAS_CASES = ["llama3-8b", "mixtral-8x7b"]


@pytest.mark.parametrize("arch", PALLAS_CASES)
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_align_target_len_padding_masked(arch, attn_impl):
    """align_prefill_cache's ``target_len`` padding path: padded slots
    must carry pos = -1 and be masked out of attention — decoding against
    a generously over-padded cache gives the same logits as a snug one,
    in both decode impls."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config(arch), attn_impl=attn_impl)
    B, T, Tp = 2, 22, 14
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)

    prefill = make_prefill_step(dataclasses.replace(cfg, attn_impl="xla"))
    _, cache = prefill(params, toks[:, :Tp])
    snug = align_prefill_cache(cfg, cache, Tp, target_len=T)
    fat = align_prefill_cache(cfg, cache, Tp, target_len=4 * T)

    # every padded slot of every KV cache carries pos = -1
    def pads(aligned, ref):
        for ga, gr in zip(aligned["groups"], ref["groups"]):
            for ca, cr in zip(ga, gr):
                if hasattr(ca, "pos") and ca.pos is not None:
                    Sr = cr.pos.shape[-1]
                    if ca.pos.shape[-1] > Sr:
                        yield np.asarray(ca.pos[..., Sr:])

    padded_planes = list(pads(fat, snug))
    # window-capped rings (all-swa archs with window < T) never widen;
    # anything with a full-attention layer must have padded
    can_pad = any(cfg.cache_len(m, 4 * T) > cfg.cache_len(m, T)
                  for m, _ in cfg.pattern if m != "ssm" and m != "rec")
    assert bool(padded_planes) == can_pad
    for plane in padded_planes:
        np.testing.assert_array_equal(plane, -np.ones_like(plane))

    decode = make_decode_step(cfg)
    for t in range(Tp, T):
        tok = toks[:, t:t + 1]
        l_snug, snug, _ = decode(params, snug, tok, jnp.int32(t))
        l_fat, fat, _ = decode(params, fat, tok, jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(l_snug), np.asarray(l_fat), atol=1e-4, rtol=1e-4,
            err_msg=f"{arch}/{attn_impl}: padding leaks at position {t}")


@pytest.mark.parametrize("arch", PALLAS_CASES)
def test_per_sequence_pos_matches_scalar(arch):
    """decode_step with a (B,) position vector (all sequences at the same
    depth) must reproduce the scalar-pos path exactly — the continuous-
    batching signature change is a strict generalization."""
    import dataclasses
    for attn_impl in ["xla", "pallas"]:
        cfg = dataclasses.replace(get_smoke_config(arch),
                                  attn_impl=attn_impl)
        B, T, Tp = 2, 20, 12
        params = M.init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
        prefill = make_prefill_step(
            dataclasses.replace(cfg, attn_impl="xla"))
        _, cache = prefill(params, toks[:, :Tp])
        cache = align_prefill_cache(cfg, cache, Tp, target_len=T)
        cache_v = cache
        decode = make_decode_step(cfg)
        for t in range(Tp, T):
            tok = toks[:, t:t + 1]
            l_s, cache, _ = decode(params, cache, tok, jnp.int32(t))
            l_v, cache_v, _ = decode(params, cache_v, tok,
                                     jnp.full((B,), t, jnp.int32))
            np.testing.assert_allclose(
                np.asarray(l_s), np.asarray(l_v), atol=1e-4, rtol=1e-4,
                err_msg=f"{arch}/{attn_impl}: vector pos diverges at {t}")


@pytest.mark.parametrize("arch", PALLAS_CASES)
def test_pallas_decode_matches_teacher_forcing(arch):
    """Multi-step decode through the fused Pallas kernel (interpret mode on
    CPU) must track teacher-forced logits exactly like the XLA path —
    including ring wrap-around on the sliding-window arch."""
    import dataclasses
    cfg = get_smoke_config(arch)
    B, T, Tp = 2, 24, 16
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)

    hidden, _, _ = M.forward(cfg, params, toks)
    tf_logits = M.logits_fn(cfg, params, hidden)

    prefill = make_prefill_step(cfg)
    _, cache = prefill(params, toks[:, :Tp])
    cache = align_prefill_cache(cfg, cache, Tp, target_len=T)

    decode = make_decode_step(dataclasses.replace(cfg, attn_impl="pallas"))
    for t in range(Tp, T):
        logits_d, cache, _ = decode(params, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(tf_logits[:, t]),
            atol=2e-2, rtol=2e-2,
            err_msg=f"{arch}: fused decode diverges at position {t}")
