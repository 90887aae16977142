"""Compile the serving path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so kernels the
chip would refuse (block shapes off the (8, 128) tiling, too much VMEM)
fail here, on the CPU, before any run on the chip.  Nothing executes.

Shapes are the engine's at SmolLM-360M widths (15 query / 5 KV heads,
head_dim 64, bf16, 8 decode slots, a 2048-position budget, 128-position
pages), plus one head_dim-128 GQA width (32 / 8 heads); the paged decode
kernel at both benchmark cells' widths.

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
