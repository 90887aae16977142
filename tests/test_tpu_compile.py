"""Compile the serving path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so kernels the
chip would refuse (block shapes off the (8, 128) tiling, too much VMEM)
fail here, on the CPU, before any run on the chip.  Nothing executes.

Shapes are the engine's at SmolLM-360M widths (15 query / 5 KV heads,
head_dim 64, bf16, 8 decode slots, a 2048-position budget, 128-position
pages), plus one head_dim-128 GQA width (32 / 8 heads); the paged decode
kernel at both benchmark cells' widths; and Mellum2's widths (32 / 4
heads of 128, window and full arenas; the grouped expert matmul at
64 experts of 2304 × 896, decode and prefill rows), and a decode step of
Mellum2's layer pattern, whose expert weights must be read in place.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_paged_pallas, decode_attention_pallas)
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.grouped_matmul.grouped_matmul import (
    grouped_matmul_pallas, plan_visits)
from repro.kernels.grouped_matmul.ops import padded_rows, row_tile
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# B, Hq, Hkv, D, S (dense ring) / pages of 128 covering S (paged)
DECODE_WIDTHS = {"smollm-360m": (8, 15, 5, 64, 2048),
                 "gqa-hd128": (8, 32, 8, 128, 2048)}


@pytest.mark.parametrize("width", sorted(DECODE_WIDTHS))
def test_dense_decode_compiles(one_chip, width):
    B, Hq, Hkv, D, S = DECODE_WIDTHS[width]
    fn = functools.partial(decode_attention_pallas, block_kv=256)
    _compile(fn, one_chip,
             ((B, Hq, 1, D), BF16), ((B, Hkv, S, D), BF16),
             ((B, Hkv, S, D), BF16), ((B, S), I32),
             ((B, Hkv, 1, D), BF16), ((B, Hkv, 1, D), BF16),
             ((B,), I32), ((B,), I32))


# B, Hq, Hkv, D, n_ptes (128-position pages), pool pages: the benchmark
# cells' decode widths (64 slots; 16 slots)
PAGED_WIDTHS = {"smollm-360m": (64, 15, 5, 64, 16, 384),
                "qwen3-8b": (16, 32, 8, 128, 24, 256)}


@pytest.mark.parametrize("width", sorted(PAGED_WIDTHS))
def test_paged_decode_compiles(one_chip, width):
    B, Hq, Hkv, D, n_ptes, pool = PAGED_WIDTHS[width]
    ps = 128
    n_pages = pool + 1                    # + the null page
    _compile(decode_attention_paged_pallas, one_chip,
             ((B, Hq, 1, D), BF16), ((n_pages, Hkv, ps, D), BF16),
             ((n_pages, Hkv, ps, D), BF16), ((n_pages, ps), I32),
             ((B, Hkv, 1, D), BF16), ((B, Hkv, 1, D), BF16),
             ((B, n_ptes), I32), ((B,), I32), ((B,), I32))


# B, Hq, Hkv, D, T (prefill length bucket)
FLASH_WIDTHS = [("smollm-360m", 1, 15, 5, 64, 128),
                ("smollm-360m", 1, 15, 5, 64, 1024),
                ("smollm-360m-batch2", 2, 15, 5, 64, 512),
                ("gqa-hd128", 1, 32, 8, 128, 512)]


@pytest.mark.parametrize("width,B,Hq,Hkv,D,T", FLASH_WIDTHS,
                         ids=[f"{w[0]}-T{w[-1]}" for w in FLASH_WIDTHS])
def test_flash_prefill_pos_planes_compiles(one_chip, width, B, Hq, Hkv, D,
                                           T):
    def fn(q, k, v, qp, kp):
        return flash_attention_pallas(q, k, v, causal=True, q_pos=qp,
                                      k_pos=kp)
    _compile(fn, one_chip,
             ((B, Hq, T, D), BF16), ((B, Hkv, T, D), BF16),
             ((B, Hkv, T, D), BF16), ((B, T), I32), ((B, T), I32))


@pytest.mark.parametrize("rows", [8, 1024])
def test_rmsnorm_compiles(one_chip, rows):
    d = 960                               # SmolLM-360M d_model
    _compile(rmsnorm_pallas, one_chip, ((rows, d), BF16), ((d,), BF16))


# Mellum2-12B-A2.5B's cell: 32 slots, 32 / 4 heads of 128; the window
# arena's 8-page ring (window 1024) and the full arena's 24 pages
MELLUM_PAGED = {"window": (8, 256, 1024), "full": (24, 768, None)}


@pytest.mark.parametrize("arena", sorted(MELLUM_PAGED))
def test_paged_decode_compiles_mellum(one_chip, arena):
    n_ptes, pool, window = MELLUM_PAGED[arena]
    B, Hq, Hkv, D, ps = 32, 32, 4, 128, 128
    fn = functools.partial(decode_attention_paged_pallas, window=window)
    _compile(fn, one_chip,
             ((B, Hq, 1, D), BF16), ((pool + 1, Hkv, ps, D), BF16),
             ((pool + 1, Hkv, ps, D), BF16), ((pool + 1, ps), I32),
             ((B, Hkv, 1, D), BF16), ((B, Hkv, 1, D), BF16),
             ((B, n_ptes), I32), ((B,), I32), ((B,), I32))


# pairs (rows × top-8): a 32-row decode step, a 1024-token prefill;
# gate/up (2304 → 896) and down (896 → 2304)
GMM_SHAPES = [(pairs, k, n) for pairs in (32 * 8, 1024 * 8)
              for k, n in ((2304, 896), (896, 2304))]


@pytest.mark.parametrize("pairs,k,n", GMM_SHAPES,
                         ids=[f"P{p}-{k}x{n}" for p, k, n in GMM_SHAPES])
def test_grouped_matmul_compiles(one_chip, pairs, k, n):
    # the expert stack of a group of two repeats, as the cell's scan has
    C, E = 2, 64
    m = padded_rows(pairs)

    def fn(lhs, rhs, sizes, layer):
        return grouped_matmul_pallas(
            lhs, rhs, plan_visits(sizes, m, row_tile(m)), layer,
            tm=row_tile(m), out_dtype=BF16)
    _compile(fn, one_chip, ((m, k), BF16), ((C, E, k, n), BF16),
             ((E,), I32), ((1,), I32))


def test_decode_reads_experts_in_place(one_chip, monkeypatch):
    """A scanned group hands the grouped kernel its whole expert stacks:
    the compiled decode step holds no array of one layer's experts, so
    no scan step copies a layer's expert matrices out of the stack
    before the kernel reads the few it was routed to.  Mellum2's layer
    pattern and kernels at small widths (two repeats of four layers)."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.grouped_matmul import ops as gops
    from repro.models import model as M
    from repro.serve import step as S

    for mod in (gops, dops, fops):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(
        get_config("mellum2-12b", num_layers=8, attn_impl="pallas"),
        d_model=256, n_heads=2, n_kv_heads=1, d_ff=128, n_experts=8,
        top_k=2, vocab=512, window=128)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: M.cache_init(cfg, 8, 256)))
    i32 = [jax.ShapeDtypeStruct(s, I32, sharding=one_chip)
           for s in ((8, 1), (8,))]
    text = S._cached_decode(cfg, None).lower(
        params, cache, *i32).compile().as_text()
    assert "_moe_gmm" in text
    assert not re.findall(r"bf16\[8,256,128\]|bf16\[8,128,256\]", text)
