"""RoPE by layer kind: YaRN tables against an inline transcription of
Hugging Face's ``_compute_yarn_parameters``, plain tables bit-identical
to the formula every configuration had before, and each kind of layer
of a model taking its own kind's table."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import layers as L


def hf_yarn(base, dim, factor, original, beta_fast, beta_slow,
            attention_factor):
    """``_compute_yarn_parameters`` (transformers), transcribed in numpy:
    truncated correction range, linear ramp, interpolation blended with
    extrapolation."""
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_extrapolation = 1.0 / pos_freqs
    inv_interpolation = 1.0 / (factor * pos_freqs)

    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    ramp = np.clip(linear, 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = inv_interpolation * (1 - extrapolation_factor) \
        + inv_extrapolation * extrapolation_factor
    return inv_freq, attention_factor


@pytest.mark.parametrize("arch,kind", [("mellum2-12b", "full"),
                                       ("mellum2-12b", "swa")])
def test_yarn_tables_equal_hf(arch, kind):
    cfg = get_config(arch)
    spec = cfg.rope_for(kind)
    inv, scale = L.rope_tables(spec, cfg.head_dim)
    if spec.factor == 1.0:
        want = 1.0 / spec.theta ** (np.arange(0, cfg.head_dim, 2)
                                    / cfg.head_dim)
        want_scale = 1.0
    else:
        want, want_scale = hf_yarn(spec.theta, cfg.head_dim, spec.factor,
                                   spec.original_len, spec.beta_fast,
                                   spec.beta_slow, spec.attention_factor)
        # published numbers: factor 16 over 8,192 positions, θ 5e5
        assert (spec.factor, spec.original_len, spec.theta) == \
            (16.0, 8192, 5e5)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert scale == pytest.approx(want_scale, rel=1e-12)


def old_rope(x, positions, theta):
    """The RoPE every configuration used before kinds had their own."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


@pytest.mark.parametrize("arch", ["qwen3-8b", "smollm-360m"])
def test_plain_tables_bit_identical(arch):
    cfg = get_config(arch)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 37, cfg.head_dim),
                          jnp.bfloat16)
    pos = jnp.arange(37) * 53
    for kind in ("full", "swa"):
        new = L.rope(x, pos, cfg.rope_for(kind))
        old = old_rope(x, pos, cfg.rope_theta)
        assert np.array_equal(np.asarray(new), np.asarray(old)), kind


def test_each_kind_takes_its_own_table():
    """In the smoke model the full layers rotate by YaRN and scale by the
    attention factor; the window layers by plain RoPE."""
    cfg = get_smoke_config("mellum2-12b")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 40, cfg.head_dim))
    pos = jnp.arange(40)
    plain = L.rope(x, pos, L.Rope(cfg.rope_theta))
    np.testing.assert_array_equal(np.asarray(L.rope(x, pos,
                                                    cfg.rope_for("swa"))),
                                  np.asarray(plain))
    full = L.rope(x, pos, cfg.rope_for("full"))
    # the position-0 rotation is the identity: only the scale is left
    np.testing.assert_allclose(np.asarray(full[..., 0, :]),
                               np.asarray(x[..., 0, :]) * 1.1386294361119891,
                               rtol=1e-6)
    assert not np.allclose(np.asarray(full[..., 5:, :]),
                           np.asarray(plain[..., 5:, :]) * 1.1386294361119891)
    no_scale = dataclasses.replace(cfg.rope_for("full"),
                                   attention_factor=1.0)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(L.rope(x, pos, no_scale))
                               * 1.1386294361119891, rtol=1e-5, atol=1e-6)
