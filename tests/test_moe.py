"""MoE routing: the pair sort against a naive one, dropless dispatch
(every real token's pairs computed, whatever the skew), padding tokens
routed nowhere, equivalence with a dense MLP when all experts share
weights, and gradients through both grouped-matmul impls."""

import dataclasses

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.models.layers import _act, init_tree, mlp
from repro.models.moe import moe_ffn, moe_tpl, route, sort_pairs

KEY = jax.random.PRNGKey(5)
IMPLS = ("xla", "pallas")


def naive_sort(experts, valid, n_experts):
    """Pairs (token-major) grouped by expert in token order, invalid
    tokens' pairs last; group sizes of the valid pairs."""
    N, K = experts.shape
    pairs = [(int(experts[t, j]) if valid[t] else n_experts, t * K + j)
             for t in range(N) for j in range(K)]
    order = [p for _, p in sorted(pairs, key=lambda x: (x[0], x[1]))]
    sizes = np.bincount([e for e, _ in pairs if e < n_experts],
                        minlength=n_experts)
    return np.array(order), sizes


@dataclasses.dataclass
class Cfg:
    n_experts: int = 4
    top_k: int = 2
    d_ff: int = 32
    act: str = "silu"
    norm_topk_prob: bool = True
    attn_impl: str = "xla"


def dense_moe(p, x, cfg, valid=None):
    """Every expert over every token, weighted by the routed gates
    (zero for experts a token was not routed to): no dispatch at all."""
    B, T, D = x.shape
    xf = x.reshape(-1, D)
    logits = jnp.dot(xf, p["router"], preferred_element_type=jnp.float32)
    _, gates, experts = route(logits, cfg.top_k, cfg.norm_topk_prob)
    w = jnp.zeros((xf.shape[0], cfg.n_experts)).at[
        jnp.arange(xf.shape[0])[:, None], experts].set(gates)
    out = jnp.zeros(xf.shape, jnp.float32)
    for e in range(cfg.n_experts):
        h = _act(xf @ p["w_gate"][e], cfg.act) * (xf @ p["w_in"][e])
        out = out + w[:, e:e + 1] * (h @ p["w_out"][e])
    if valid is not None:
        out = jnp.where(valid.reshape(-1, 1), out, 0)
    return out.reshape(B, T, D)


class TestSort:
    @given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 7),
                              st.booleans()), min_size=1, max_size=48))
    @settings(max_examples=60, deadline=None)
    def test_sort_pairs_matches_naive(self, rows):
        """Expert-sorted pair order and per-expert sizes equal a naive
        sort (stable in token order; invalid tokens' pairs last)."""
        experts = np.array([[a, b] for a, b, _ in rows], np.int32)
        valid = np.array([v for _, _, v in rows])
        order, inv, sizes = sort_pairs(jnp.asarray(experts),
                                       jnp.asarray(valid), 8)
        want_order, want_sizes = naive_sort(experts, valid, 8)
        np.testing.assert_array_equal(np.asarray(order), want_order)
        np.testing.assert_array_equal(np.asarray(sizes), want_sizes)
        np.testing.assert_array_equal(np.asarray(order)[np.asarray(inv)],
                                      np.arange(experts.size))


class TestMoE:
    @pytest.mark.parametrize("impl", IMPLS)
    def test_equals_dense_when_experts_identical(self, impl):
        """With identical expert weights MoE == MLP (gates sum to 1)."""
        cfg = Cfg(attn_impl=impl)
        D = 16
        tpl = moe_tpl(D, cfg.d_ff, cfg.n_experts, "float32", glu=True)
        p = init_tree(tpl, KEY)
        # make every expert identical to expert 0
        for k in ("w_in", "w_out", "w_gate"):
            p[k] = jnp.broadcast_to(p[k][0][None], p[k].shape)
        x = jax.random.normal(KEY, (2, 8, D))
        out, aux, counts = moe_ffn(p, x, cfg)
        dense_p = {"w_in": p["w_in"][0], "w_out": p["w_out"][0],
                   "w_gate": p["w_gate"][0]}
        ref = mlp(dense_p, x, act="silu", glu=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert float(aux) > 0
        assert list(np.asarray(counts)) == [2 * 8 * 2,
                                            int(np.asarray(counts)[1])]

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("skew", ["one_expert", "uniform"])
    def test_capacity_drops_tokens(self, impl, skew):
        """Dropless: every real token's pairs are computed, however the
        router skews.  ``one_expert`` routes all 64 tokens of a row to
        expert 0 (a capacity of 1.25 × T·K/E would have kept 40 of
        them); the output equals the dense all-experts reference."""
        cfg = Cfg(top_k=1, attn_impl=impl)
        D = 16
        p = init_tree(moe_tpl(D, cfg.d_ff, cfg.n_experts, "float32"), KEY)
        x = jax.random.normal(KEY, (2, 64, D))
        if skew == "one_expert":
            # a router that scores expert 0 far above the rest everywhere
            p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
            x = jnp.abs(x) + 1.0
        out, _, counts = moe_ffn(p, x, cfg)
        ref = dense_moe(p, x, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert int(counts[0]) == 2 * 64
        if skew == "one_expert":
            assert int(counts[1]) == 1

    @pytest.mark.parametrize("impl", IMPLS)
    def test_padding_tokens_routed_nowhere(self, impl):
        """Invalid tokens take no pair: the counts leave them out, their
        rows come out zero, and the real rows are unchanged."""
        cfg = Cfg(attn_impl=impl)
        D = 16
        p = init_tree(moe_tpl(D, cfg.d_ff, cfg.n_experts, "float32"), KEY)
        x = jax.random.normal(KEY, (2, 8, D))
        valid = jnp.arange(8)[None, :] < jnp.array([[5], [2]])
        out, _, counts = moe_ffn(p, x, cfg, valid)
        full, _, _ = moe_ffn(p, x, cfg)
        v = np.asarray(valid)
        np.testing.assert_allclose(np.asarray(out)[v], np.asarray(full)[v],
                                   atol=1e-5, rtol=1e-5)
        assert not np.asarray(out)[~v].any()
        assert int(counts[0]) == 7 * cfg.top_k

    def test_norm_topk_prob_off_keeps_softmax_gates(self):
        """``norm_topk_prob=False`` leaves the top-k softmax weights as
        they are (they sum below one)."""
        logits = jnp.array([[2.0, 1.0, 0.5, -1.0]])
        _, g_on, _ = route(logits, 2, True)
        _, g_off, e = route(logits, 2, False)
        probs = jax.nn.softmax(logits)
        np.testing.assert_allclose(np.asarray(g_off),
                                   np.asarray(probs[0, e[0]])[None])
        assert float(g_off.sum()) < 1.0
        np.testing.assert_allclose(float(g_on.sum()), 1.0, rtol=1e-6)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_grads_flow(self, impl):
        cfg = Cfg(attn_impl=impl)
        D = 16
        p = init_tree(moe_tpl(D, cfg.d_ff, cfg.n_experts, "float32"), KEY)
        x = jax.random.normal(KEY, (1, 16, D))

        def loss(p):
            out, aux, _ = moe_ffn(p, x, cfg)
            return (out ** 2).sum() + aux

        g = jax.grad(loss)(p)
        gn = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
