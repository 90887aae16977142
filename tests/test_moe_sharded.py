"""The expert layer on a mesh (``moe._mixture_sharded``) against the same
layer without one: the same outputs, gradients and counts under each
rule table, with the experts or the expert width sharded, and each
device sorting only the pairs of its own tokens.

The mesh needs several devices, and the CPU backend gives a process its
device count only at start-up, so each case runs in a child process with
eight host devices (a 2 × 4 ``data`` × ``model`` mesh)."""

from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

CHILD = r"""
import dataclasses, json, re, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec
from repro.configs import get_smoke_config
from repro.dist.sharding import ShardCtx, rules_variant, use_ctx
from repro.models import moe as MOE

arch, E, impl = sys.argv[1], int(sys.argv[2]), sys.argv[3]
cfg = dataclasses.replace(get_smoke_config(arch), n_experts=E,
                          attn_impl=impl)
tpl = MOE.moe_tpl(64, 128, E, "float32", True, cfg.shared_expert)
keys = jax.random.split(jax.random.PRNGKey(0), len(tpl))
p = {k: jax.random.normal(kk, t.shape) * 0.2
     for (k, t), kk in zip(sorted(tpl.items()), keys)}
x = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 64))
valid = jax.random.uniform(jax.random.PRNGKey(2), (8, 6)) > 0.2


def f(p, x):
    o, a, c = MOE.moe_ffn(p, x, cfg, valid)
    return (o ** 2).sum() + a, (o, c)


(_, (o0, c0)), g0 = jax.value_and_grad(f, has_aux=True)(p, x)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
out = {}
for rules in ("fsdp", "tp", "moe_tp"):
    ctx = ShardCtx(mesh, rules_variant(rules))
    ps = {k: jax.device_put(v, ctx.sharding(tpl[k].logical, tpl[k].shape))
          for k, v in p.items()}
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec("data")))

    def fs(p, x):
        with use_ctx(ctx):
            return jax.value_and_grad(f, has_aux=True)(p, x)

    step = jax.jit(fs)
    (_, (o1, c1)), g1 = step(ps, xs)
    hlo = step.lower(ps, xs).compile().as_text()
    sorts = sorted({int(n) for n in re.findall(
        r"= \(s32\[(\d+)\][^)]*\) sort\(", hlo)})
    out[rules] = {
        "spec": str(ctx.spec(tpl["w_in"].logical, tpl["w_in"].shape)),
        "out": float(jnp.abs(o0 - o1).max() / jnp.abs(o0).max()),
        "grad": {k: float(jnp.abs(g0[k] - g1[k]).max()
                          / jnp.abs(g0[k]).max()) for k in g0},
        "counts": [np.asarray(c0).tolist(), np.asarray(c1).tolist()],
        "sorts": sorts}
print(json.dumps(out))
"""

CASES = [("mixtral-8x7b", 8, "xla"),     # experts over the model axis
         ("mixtral-8x7b", 8, "pallas"),  # the grouped kernel per shard
         ("mixtral-8x7b", 2, "xla"),     # 2 experts: the width shards
         ("llama4-maverick-400b-a17b", 8, "xla")]   # top-1 + shared


@pytest.mark.parametrize("arch,experts,impl", CASES)
def test_sharded_experts_match_unsharded(arch, experts, impl):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(SRC))
    run = subprocess.run([sys.executable, "-c", CHILD, arch, str(experts),
                          impl], env=env, capture_output=True, text=True,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    res = json.loads(run.stdout.strip().splitlines()[-1])
    k = 1 if arch.startswith("llama4") else 2
    for rules, r in res.items():
        assert r["out"] < 1e-5, (rules, r)
        for name, err in r["grad"].items():
            # top-1 gates are x / x: their gradient is a cancellation
            # whose rounding is scaled by the output's gradient
            tol = 5e-2 if (name == "router" and k == 1) else 1e-5
            assert err < tol, (rules, name, err)
        assert r["counts"][0] == r["counts"][1], (rules, r["counts"])
        # each device sorts the pairs of its 4 of the 8 rows (6 tokens,
        # k experts each), and of all 8 where the experts share the
        # batch's mesh axis and the tokens are gathered over it
        gathered = "data" in re.sub(r"\s", "", r["spec"])
        want = (8 if gathered else 4) * 6 * k
        assert r["sorts"] and max(r["sorts"]) <= want, (rules, r)
