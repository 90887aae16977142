"""The grouped expert matmul (``kernels/grouped_matmul``) in interpret
mode against a per-expert einsum: empty groups, a group larger than a
row tile, groups straddling tiles, padding rows past every group; the
visit plan against a naive walk; gradients through the kernel."""

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels.grouped_matmul.grouped_matmul import plan_visits
from repro.kernels.grouped_matmul.ops import (TILE_M, grouped_matmul,
                                              make_plan, padded_rows)


def per_expert(lhs, rhs, sizes):
    """Row block ``g`` of the sorted rows times ``rhs[g]``, one einsum per
    expert; rows past every group zero."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for g, n in enumerate(sizes):
        out[start:start + n] = np.einsum("mk,kn->mn", lhs[start:start + n],
                                         rhs[g])
        start += n
    return out


# per case: group sizes (experts 0..G-1) and padding rows after them
CASES = {
    "empty_groups": ([0, 3, 0, 0, 9, 0, 1, 0], 4),
    "group_over_a_tile": ([5, TILE_M + 70, 0, 2], 0),
    "straddling": ([60, 60, 60, 60, 7], 13),
    "one_group_all_rows": ([0, 0, 200, 0], 0),
    "all_empty": ([0, 0, 0], 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_per_expert_einsum(case):
    sizes, pad = CASES[case]
    rng = np.random.default_rng(len(case))
    real = sum(sizes)
    m = padded_rows(max(real + pad, 1))
    G, k, n = len(sizes), 48, 40
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((G, k, n)).astype(np.float32)
    want = per_expert(lhs, rhs, sizes)
    for impl in ("pallas", "xla"):
        plan = make_plan(jnp.asarray(sizes, jnp.int32), m, impl)
        got = np.asarray(grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                        plan, out_dtype=jnp.float32))
        np.testing.assert_allclose(got[:real], want[:real], rtol=1e-5,
                                   atol=1e-4, err_msg=impl)


@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_its_repeat_of_a_stack(layer):
    """With the expert stack of a scanned group (C, G, k, n) and a repeat
    index, the kernel multiplies by that repeat's experts: the same
    product as handing it the sliced (G, k, n) stack."""
    sizes = [3, 0, 20, 9]
    rng = np.random.default_rng(layer)
    m = padded_rows(sum(sizes))
    lhs = rng.standard_normal((m, 24)).astype(np.float32)
    stack = rng.standard_normal((3, len(sizes), 24, 16)).astype(np.float32)
    want = per_expert(lhs, stack[layer], sizes)
    for impl in ("pallas", "xla"):
        plan = make_plan(jnp.asarray(sizes, jnp.int32), m, impl)
        got = np.asarray(grouped_matmul(
            jnp.asarray(lhs), jnp.asarray(stack), plan, jnp.float32,
            layer=jnp.int32(layer)))
        np.testing.assert_allclose(got[:sum(sizes)], want[:sum(sizes)],
                                   rtol=1e-5, atol=1e-4, err_msg=impl)


def naive_visits(sizes, m, tm):
    out, start = [], 0
    for g, n in enumerate(sizes):
        for t in range(m // tm):
            if n and start < (t + 1) * tm and start + n > t * tm:
                out.append((g, t))
        start += n
    return out


@given(st.lists(st.integers(0, 40), min_size=1, max_size=12),
       st.sampled_from([16, 32]))
@settings(max_examples=60, deadline=None)
def test_visit_plan_matches_naive(sizes, tm):
    """The plan visits exactly the (group, tile) pairs holding rows, in
    group order (so a shared tile's visits are consecutive), and never
    an empty group; cells past the real visits repeat the last one."""
    m = max(tm, -(-sum(sizes) // tm) * tm)
    v = plan_visits(jnp.asarray(sizes, jnp.int32), m, tm)
    want = naive_visits(sizes, m, tm)
    count = int(v.count[0])
    got = list(zip(np.asarray(v.group)[:count].tolist(),
                   np.asarray(v.tile)[:count].tolist()))
    assert got == want
    assert v.group.shape[0] == m // tm + len(sizes) - 1
    if count:
        assert (np.asarray(v.group)[count:] == want[-1][0]).all()
        assert (np.asarray(v.tile)[count:] == want[-1][1]).all()
    assert int(np.asarray(v.offsets)[-1]) == sum(sizes)


def test_gradients_match_the_oracle():
    """The kernel's backward pass is the ``ragged_dot`` oracle's."""
    rng = np.random.default_rng(3)
    sizes = jnp.asarray([7, 0, 20, 5], jnp.int32)
    m = padded_rows(40)
    lhs = jnp.asarray(rng.standard_normal((m, 16)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    mask = (jnp.arange(m) < 32)[:, None]

    def loss(impl):
        plan = make_plan(sizes, m, impl)
        return lambda a, b: jnp.sum(jnp.where(
            mask, grouped_matmul(a, b, plan, jnp.float32), 0.0) ** 2)

    gk = jax.grad(loss("pallas"), argnums=(0, 1))(lhs, rhs)
    gx = jax.grad(loss("xla"), argnums=(0, 1))(lhs, rhs)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    # from a stack of repeats: the gradient lands on the repeat read
    stack = jnp.stack([rhs * 2, rhs])

    def stacked(a, s):
        plan = make_plan(sizes, m, "pallas")
        return jnp.sum(jnp.where(mask, grouped_matmul(
            a, s, plan, jnp.float32, layer=jnp.int32(1)), 0.0) ** 2)

    ga, gs = jax.grad(stacked, argnums=(0, 1))(lhs, stack)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gk[0]),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs[1]), np.asarray(gk[1]),
                               rtol=1e-5, atol=1e-4)
    assert not np.asarray(gs[0]).any()
