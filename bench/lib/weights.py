"""Seeded weights, owned by the benchmark.

Every weight is a function of ``(seed, name, layer)`` alone:
``leaf(key, leaves, name, layer, shape)`` draws it in bfloat16, where
``layer`` is the model layer's number, counted from the input.  The
program gets them stacked in the layout of its own parameter tree, made
on the device in one jitted call; the reference draws the same leaves one
layer at a time, so it shares no array with the program and still sees
the same numbers.

The program stacks its layers by group and by position in the group's
pattern: ``groups[g]["layers"][p]`` holds, at index ``s`` of its leading
axis, model layer ``(layers of the groups before g) + s · P + p`` for a
pattern of ``P`` positions.  The layer number is read off the shape tree
alone.

``leaves`` is the reference module's ``LEAVES``: every weight name of its
family of models (``wq``, ``w_gate``, ...) in a fixed order, each with
its kind.  A name's place in that order picks its stream of random
numbers; a program leaf is named by the last key of its path.  Scales by
kind keep a random model's activations and logits in the ranges of a
trained one: ``matrix`` at ``1/sqrt(fan_in)``, the size of the axis it
contracts over (``shape[-2]``: ``d`` of a ``(d, f)`` matrix and of an
``(experts, d, f)`` stack alike), ``norm`` weights
``1 + 0.1·N(0, 1)`` so that skipping a norm's weight changes the result,
and ``embed`` so that logits have a standard deviation of about 3.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_STD = 3.0


def base_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def leaf(key: jax.Array, leaves: Mapping[str, str], name: str, layer,
         shape: Tuple[int, ...]) -> jax.Array:
    """Weight ``name`` of ``layer`` (0 for the unstacked leaves), bf16.
    Traceable in ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(key, list(leaves).index(name)),
                           layer)
    z = jax.random.normal(k, shape, jnp.float32)
    kind = leaves[name]
    if kind == "norm":
        w = 1.0 + 0.1 * z
    elif kind == "embed":
        w = z * (LOGIT_STD / math.sqrt(shape[-1]))
    elif kind == "matrix":
        w = z * (1.0 / math.sqrt(shape[-2]))
    else:
        raise ValueError(f"weight {name!r} has unknown kind {kind!r}")
    return w.astype(jnp.bfloat16)


def _name_of(path, leaves: Mapping[str, str]) -> str:
    for p in reversed(path):
        key = getattr(p, "key", None)
        if isinstance(key, str):
            if key not in leaves:
                raise KeyError(f"no seeded weight for program leaf {key!r}")
            return key
    raise KeyError(f"program leaf at {path} has no name")


def _first_layers(groups) -> List[int]:
    """The model layer number of each group's first layer: the layers of
    the groups before it, each ``count × pattern length``."""
    out, n = [], 0
    for g in groups:
        out.append(n)
        count = jax.tree_util.tree_leaves(g["layers"][0])[0].shape[0]
        n += count * len(g["layers"])
    return out


def _position(path, first: List[int], shapes) -> Optional[Tuple[int, int]]:
    """``(layer, stride)`` of a stacked leaf: the model layer at index 0
    of its leading axis, and the step in layers from one index to the
    next; ``None`` for a leaf outside ``groups``."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    if "groups" not in keys:
        return None
    at = keys.index("groups")         # groups, g, "layers", p, ...
    g, pi = keys[at + 1], keys[at + 3]
    return first[g] + pi, len(shapes["groups"][g]["layers"])


def make_program_params(shapes, seed: int, leaves: Mapping[str, str]):
    """The program's parameter tree (``shapes``: its ``ShapeDtypeStruct``
    tree) filled with the seeded weights, in one jitted call on the
    default device."""
    key = base_key(seed)
    first = _first_layers(shapes.get("groups", []))

    def build(key):
        def one(path, s):
            name = _name_of(path, leaves)
            pos = _position(path, first, shapes)
            if pos is not None:
                layers = pos[0] + jnp.arange(s.shape[0]) * pos[1]
                return jax.vmap(lambda l: leaf(
                    key, leaves, name, l, s.shape[1:]))(layers).astype(s.dtype)
            return leaf(key, leaves, name, 0, s.shape).astype(s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(build)(key)


def layer_fn(seed: int, leaves: Mapping[str, str],
             shapes: Dict[str, Tuple[int, ...]]
             ) -> Callable[[int], Dict[str, jax.Array]]:
    """``f(layer) -> {name: f32 array}`` for the reference: one layer's
    weights drawn by the same rule, in one jitted call per layer."""
    key = base_key(seed)

    @jax.jit
    def draw(layer):
        return {n: leaf(key, leaves, n, layer, s).astype(jnp.float32)
                for n, s in shapes.items()}

    return lambda layer: draw(jnp.int32(layer))


def draw_one(seed: int, leaves: Mapping[str, str], name: str,
             shape: Tuple[int, ...]) -> jax.Array:
    """An unstacked weight (embedding, head, final norm) in f32."""
    return jax.jit(lambda k: leaf(k, leaves, name, 0, shape).astype(
        jnp.float32))(base_key(seed))
