"""Shared model layers (pure-functional JAX, no framework dependency).

Parameters are plain dict pytrees; every creator returns a *template*
``(shape, logical_axes, init)`` so the same source of truth serves real
initialization (smoke tests/training) and ShapeDtypeStruct specs (dry-run).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import shard_activation
from ..kernels.rmsnorm.ops import rmsnorm as rmsnorm_op


# ---------------------------------------------------------------- templates --

@dataclasses.dataclass(frozen=True)
class ParamTpl:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small_normal
    dtype: str = "bfloat16"

    def initialize(self, key) -> jax.Array:
        dt = jnp.dtype(self.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return jnp.ones(self.shape, dt)
        fan_in = self.shape[0] if len(self.shape) >= 2 else \
            max(1, self.shape[-1])
        std = 0.02 if self.init == "small_normal" else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, self.shape, jnp.float32) * std
                ).astype(dt)


def init_tree(tpl_tree, key):
    leaves, treedef = jax.tree.flatten(
        tpl_tree, is_leaf=lambda x: isinstance(x, ParamTpl))
    keys = jax.random.split(key, len(leaves))
    vals = [l.initialize(k) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def stack_tpl(tpl_tree, n: int):
    """Prefix every template with a scan (layers) dimension of size n."""
    return jax.tree.map(
        lambda t: ParamTpl((n,) + t.shape, ("layers",) + t.logical,
                           t.init, t.dtype),
        tpl_tree, is_leaf=lambda x: isinstance(x, ParamTpl))


# ---------------------------------------------------------------- norms ------

def rmsnorm(x, w, eps: float = 1e-6, plus_one: bool = False,
            impl: str = "xla"):
    return rmsnorm_op(x, w, eps=eps, plus_one=plus_one, impl=impl)


def rmsnorm_tpl(d: int, dtype: str) -> ParamTpl:
    return ParamTpl((d,), ("embed",), "ones" , dtype)


# ---------------------------------------------------------------- rope -------

@dataclasses.dataclass(frozen=True)
class Rope:
    """RoPE of one kind of attention layer: base ``theta`` and, with
    ``factor > 1``, YaRN scaling (Peng et al. 2023) as Hugging Face's
    ``_compute_yarn_parameters`` computes it: the inverse frequencies
    blend ``inv_freq / factor`` (interpolation) with ``inv_freq``
    (extrapolation) along a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations over ``original_len``
    positions, and cos and sin are scaled by ``attention_factor``."""
    theta: float = 1e4
    factor: float = 1.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rope_tables(spec: Rope, d: int) -> Tuple[jax.Array, float]:
    """``(inverse frequencies (d/2,) f32, cos/sin scale)`` of ``spec`` for
    heads of ``d``; plain RoPE's frequencies are ``theta ** (-i / (d/2))``
    exactly as they have always been computed here."""
    half = d // 2
    freqs = spec.theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if spec.factor == 1.0:
        return freqs, spec.attention_factor

    def corr_dim(rotations):
        return (d * math.log(spec.original_len / (rotations * 2 * math.pi))
                ) / (2 * math.log(spec.theta))

    low = max(math.floor(corr_dim(spec.beta_fast)), 0)
    high = min(math.ceil(corr_dim(spec.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    freqs = (freqs / spec.factor) * (1.0 - extrapolate) \
        + freqs * extrapolate
    return freqs, spec.attention_factor


def rope(x: jax.Array, positions: jax.Array, spec: Rope = Rope()
         ) -> jax.Array:
    """x: (B, H, T, D_head); positions: (B, T) or (T,)."""
    half = x.shape[-1] // 2
    freqs, scale = rope_tables(spec, x.shape[-1])
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # B1TH
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------- mlp --------

def mlp_tpl(d: int, f: int, glu: bool, dtype: str) -> Dict[str, ParamTpl]:
    tpl = {
        "w_in": ParamTpl((d, f), ("embed", "mlp"), "normal", dtype),
        "w_out": ParamTpl((f, d), ("mlp", "embed"), "normal", dtype),
    }
    if glu:
        tpl["w_gate"] = ParamTpl((d, f), ("embed", "mlp"), "normal", dtype)
    return tpl


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


def mlp(p, x, act: str = "silu", glu: bool = True):
    h = x @ p["w_in"]
    if glu:
        h = _act(x @ p["w_gate"], act) * h
    else:
        h = _act(h, act)
    h = shard_activation(h, ("batch", None, "mlp"))
    return h @ p["w_out"]


# ---------------------------------------------------------------- embed ------

def embed_tpl(vocab: int, d: int, dtype: str) -> ParamTpl:
    return ParamTpl((vocab, d), ("vocab", "embed"), "small_normal", dtype)


def embed(p: jax.Array, tokens: jax.Array, scale: bool = False) -> jax.Array:
    x = jnp.take(p, tokens, axis=0)
    if scale:
        x = x * math.sqrt(p.shape[1])
    return shard_activation(x, ("batch", "seq_ctx", "embed"))


def unembed(p: jax.Array, x: jax.Array, softcap: float = 0.0) -> jax.Array:
    logits = (x @ p.T).astype(jnp.float32)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    return shard_activation(logits, ("batch", None, "vocab"))


# ---------------------------------------------------------------- loss -------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Plain CE — logits (B,T,V) fully materialized."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def chunked_cross_entropy(x: jax.Array, emb: jax.Array, labels: jax.Array,
                          chunk: int = 1024, softcap: float = 0.0
                          ) -> jax.Array:
    """Beyond-paper memory optimization: never materialize (B,T,V) logits.

    Computes CE over sequence chunks under remat — per-chunk logits are
    (B, chunk, V) and are recomputed in the backward pass.
    """
    B, T, D = x.shape
    n = T // chunk

    @jax.checkpoint
    def one(xc, lc):
        logits = unembed(emb, xc, softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    xs = x[:, : n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1)
    ls = labels[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)

    def body(tot, xl):
        xc, lc = xl
        return tot + one(xc, lc), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    rem = T - n * chunk
    if rem:
        tot = tot + one(x[:, n * chunk:], labels[:, n * chunk:])
    return tot / (B * T)


# ---------------------------------------------------------------- linear -----

def linear_tpl(d_in: int, d_out: int, logical: Tuple, dtype: str,
               init: str = "normal") -> ParamTpl:
    return ParamTpl((d_in, d_out), logical, init, dtype)


__all__ = [
    "ParamTpl", "init_tree", "stack_tpl", "rmsnorm", "rmsnorm_tpl", "Rope",
    "rope_tables", "rope",
    "mlp", "mlp_tpl", "embed", "embed_tpl", "unembed", "cross_entropy",
    "chunked_cross_entropy", "linear_tpl",
]
