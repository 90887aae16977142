"""Per-architecture smoke tests (deliverable f): every assigned arch in a
reduced same-family config runs one forward/train step on CPU with shape
assertions and finite outputs; decode runs one step against a cache."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, get_config, get_smoke_config, \
    supports_shape
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.train.step import StepConfig, init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def make_ctx_embed(cfg, B):
    if cfg.encoder_layers:
        return jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model),
                                 jnp.float32)
    if cfg.vis_tokens:
        return jax.random.normal(KEY, (B, cfg.vis_tokens, cfg.d_model),
                                 jnp.float32)
    return None


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    B, T = 2, 32
    opt = AdamWConfig(lr=1e-3, total_steps=4, warmup_steps=1)
    state = init_train_state(cfg, opt, KEY)
    step = jax.jit(make_train_step(cfg, opt, StepConfig()))
    batch = {"tokens": jax.random.randint(KEY, (B, T), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (B, T), 0, cfg.vocab)}
    ce = make_ctx_embed(cfg, B)
    if ce is not None:
        batch["ctx_embed"] = ce
    l0 = None
    for _ in range(3):
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        assert jnp.isfinite(metrics["loss"]), arch
        l0 = loss if l0 is None else l0
    assert loss < l0 + 1e-3, f"{arch}: loss failed to move ({l0}→{loss})"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_shapes(arch):
    cfg = get_smoke_config(arch)
    B = 2
    params = M.init_params(cfg, KEY)
    cache = M.cache_init(cfg, B, 64)
    ce = make_ctx_embed(cfg, B)
    if ce is not None:
        cache["ctx_enc"] = (M.encode(cfg, params, ce)
                            if cfg.encoder_layers else
                            ce.astype(jnp.float32))
    tok = jax.random.randint(KEY, (B, 1), 0, cfg.vocab)
    logits, cache2, _ = M.decode_step(cfg, params, cache, tok, jnp.int32(7))
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    # cache structure preserved
    assert jax.tree.structure(
        {k: v for k, v in cache.items() if k != "ctx_enc"}) == \
        jax.tree.structure(
            {k: v for k, v in cache2.items() if k != "ctx_enc"})


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_metadata(arch):
    """Full configs match the assignment sheet (spot checks, no alloc)."""
    cfg = get_config(arch)
    sheet = {
        "whisper_medium": (24, 1024, 16, 16, 4096, 51865),
        "mamba2_1p3b": (48, 2048, None, None, 0, 50280),
        "qwen3_8b": (36, 4096, 32, 8, 12288, 151936),
        "llama3_8b": (32, 4096, 32, 8, 14336, 128256),
        "gemma_7b": (28, 3072, 16, 16, 24576, 256000),
        "smollm_360m": (32, 960, 15, 5, 2560, 49152),
        "mixtral_8x7b": (32, 4096, 32, 8, 14336, 32000),
        "llama4_maverick_400b_a17b": (48, 5120, 40, 8, 8192, 202048),
        "llama32_vision_11b": (40, 4096, 32, 8, 14336, 128256),
        "recurrentgemma_9b": (38, 4096, 16, 1, 12288, 256000),
        "mellum2_12b": (28, 2304, 32, 4, 896, 98304),
    }[arch]
    L, d, h, kv, ff, vocab = sheet
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.d_ff == ff and cfg.vocab == vocab
    if h is not None:
        assert cfg.n_heads == h and cfg.n_kv_heads == kv
    # layer pattern covers num_layers
    assert sum(c * len(p) for p, c in cfg.groups) == cfg.num_layers


def test_moe_active_params_below_total():
    for arch in ("mixtral_8x7b", "llama4_maverick_400b_a17b",
                 "mellum2_12b"):
        t, a = M.param_count(get_config(arch))
        assert a < t


def test_long_context_support_flags():
    runs = {a: supports_shape(get_config(a), "long_500k") for a in ARCHS}
    assert runs["mamba2_1p3b"] and runs["mixtral_8x7b"] and \
        runs["recurrentgemma_9b"]
    assert not runs["llama3_8b"] and not runs["whisper_medium"]
