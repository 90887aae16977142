"""Seeded weights (``lib/weights.py``) on the program's own shape trees:
each model layer draws its own numbers, the program's layer ``l`` is the
reference's layer ``l`` for a pattern of several kinds over two groups,
matrices are scaled by the axis they contract over, and the dense
configurations' weights are the bytes the first rule drew."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from lib import spec, weights as W
from repro.configs import get_smoke_config
from repro.models.model import ModelConfig, init_params

SEED = 2**32 + 17
MOE_LEAVES = {"embed": "embed", "unembed": "embed", "ln_f": "norm",
              "ln1": "norm", "ln2": "norm", "wq": "matrix", "wk": "matrix",
              "wv": "matrix", "wo": "matrix", "router": "matrix",
              "w_in": "matrix", "w_gate": "matrix", "w_out": "matrix"}
# (swa, moe) x 3 + (full, moe): two whole periods and a group of two
MOE = ModelConfig(
    name="swa-moe-smoke", family="moe", num_layers=10, d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96, vocab=128,
    pattern=(("swa", "moe"),) * 3 + (("full", "moe"),), window=8,
    n_experts=8, top_k=2, dtype="bfloat16")


def _shapes(cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _by_layer(cfg, params):
    """``{layer: {name: array}}``, walking the groups in the order the
    model applies them: each group's superblock ``count`` times, its
    pattern's positions in order."""
    out, layer = {}, 0
    for gi, (pattern, count) in enumerate(cfg.groups):
        for s in range(count):
            for pi in range(len(pattern)):
                leaves = jax.tree_util.tree_leaves_with_path(
                    params["groups"][gi]["layers"][pi])
                out[layer] = {path[-1].key: a[s] for path, a in leaves}
                layer += 1
    return out


@pytest.fixture(scope="module")
def moe_params():
    return W.make_program_params(_shapes(MOE), SEED, MOE_LEAVES)


def test_every_layer_draws_its_own(moe_params):
    assert [len(p) for p, _ in MOE.groups] == [4, 2]
    layers = _by_layer(MOE, moe_params)
    assert sorted(layers) == list(range(MOE.num_layers))
    for name in MOE_LEAVES:
        if name not in layers[0]:
            continue
        draws = [np.asarray(layers[l][name], np.float32).ravel()
                 for l in layers]
        for a in range(len(draws)):
            for b in range(a):
                assert not np.array_equal(draws[a], draws[b]), (name, a, b)


def test_program_layer_is_reference_layer(moe_params):
    layers = _by_layer(MOE, moe_params)
    shapes = {n: tuple(a.shape) for n, a in layers[0].items()}
    draw = W.layer_fn(SEED, MOE_LEAVES, shapes)
    for l, program in layers.items():
        ref = draw(l)
        for name, a in program.items():
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(ref[name]),
                err_msg=f"layer {l} {name}")


@pytest.mark.parametrize("name,fan_in", [
    ("w_in", 64), ("w_gate", 64), ("w_out", 96), ("router", 64), ("wo", 64)])
def test_matrices_scaled_by_fan_in(moe_params, name, fan_in):
    layers = _by_layer(MOE, moe_params)
    w = np.stack([np.asarray(layers[l][name], np.float32) for l in layers])
    assert w.std() == pytest.approx(1 / math.sqrt(fan_in), rel=0.05)


def _first_rule(shapes, seed, leaves):
    """The rule as it was first written: a stacked leaf drawn by its
    index on the stacked axis, a matrix scaled by ``shape[0]``."""
    key = W.base_key(seed)

    def leaf(name, layer, shape):
        k = jax.random.fold_in(jax.random.fold_in(key, list(leaves).index(
            name)), layer)
        z = jax.random.normal(k, shape, jnp.float32)
        kind = leaves[name]
        if kind == "norm":
            w = 1.0 + 0.1 * z
        elif kind == "embed":
            w = z * (W.LOGIT_STD / math.sqrt(shape[-1]))
        else:
            w = z * (1.0 / math.sqrt(shape[0]))
        return w.astype(jnp.bfloat16)

    def one(path, s):
        name = [p.key for p in path if isinstance(getattr(p, "key", None),
                                                  str)][-1]
        if any(getattr(p, "key", None) == "groups" for p in path):
            return jax.vmap(lambda l: leaf(name, l, s.shape[1:]))(
                jnp.arange(s.shape[0])).astype(s.dtype)
        return leaf(name, 0, s.shape).astype(s.dtype)
    return jax.jit(lambda: jax.tree_util.tree_map_with_path(one, shapes))()


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-8b"])
def test_dense_weights_unchanged(arch):
    leaves = spec.module(BENCH, "reference", "dense").LEAVES
    cfg = get_smoke_config(arch, num_layers=3, dtype="bfloat16")
    shapes = _shapes(cfg)
    got = W.make_program_params(shapes, SEED, leaves)
    want = _first_rule(shapes, SEED, leaves)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype == jnp.bfloat16
        assert np.asarray(a).view(np.uint16).tobytes() == \
            np.asarray(b).view(np.uint16).tobytes(), path
