"""Mixture-of-Experts FFN with dropless routing over a grouped matmul.

Every real token's top-k (token, expert) pairs are computed; nothing is
dropped for capacity.  The pairs are sorted by expert, the expert GEMMs
run as grouped matmuls over the sorted rows (``kernels/grouped_matmul``,
chosen by ``cfg.attn_impl``: a Pallas kernel that visits only the experts
holding rows, so a decode step reads only the experts its tokens were
routed to, or ``ragged_dot``), and each pair's output comes back to its
token weighted by its gate.

Under a sharding context with a mesh the experts run as expert
parallelism (:func:`_mixture_sharded`): each device sorts only the pairs
of its own batch shard, as the capacity dispatch this replaced kept its
sort inside each batch row, and the expert stacks stay sharded.

Padding tokens (``valid`` false: bucket padding in prefill, idle rows in
decode) take no pair: their rows sort past every expert, belong to no
group, and contribute nothing.

Inside a scan over a layer group, the expert stacks come whole, every
repeat of the group along a leading axis, with ``layer`` the repeat
being run: the grouped kernel reads that repeat's experts out of the
stack, so no scan step copies a layer's expert weights before the call.

Besides the output and the load-balancing loss, :func:`moe_ffn` returns
two counts of the call's real work, ``[pairs, experts hit]``: the serve
engine sums them into its ``moe_pairs.*`` and ``moe_experts_hit.*``
counters.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..dist.sharding import current_ctx
from ..kernels.grouped_matmul.ops import (grouped_matmul, make_plan,
                                          padded_rows)
from .layers import ParamTpl, _act

EXPERT_STACKS = ("w_in", "w_gate", "w_out")   # the grouped matmuls' weights


def moe_tpl(d: int, f: int, n_experts: int, dtype: str, glu: bool = True,
            shared_expert: bool = False) -> Dict[str, ParamTpl]:
    tpl = {
        "router": ParamTpl((d, n_experts), ("embed", None), "small_normal",
                           dtype),
        "w_in": ParamTpl((n_experts, d, f),
                         ("experts", "moe_embed", "mlp"), "normal", dtype),
        "w_out": ParamTpl((n_experts, f, d),
                          ("experts", "mlp", "moe_embed"), "normal", dtype),
    }
    if glu:
        tpl["w_gate"] = ParamTpl((n_experts, d, f),
                                 ("experts", "moe_embed", "mlp"), "normal",
                                 dtype)
    if shared_expert:
        tpl["shared_in"] = ParamTpl((d, f), ("embed", "mlp"), "normal", dtype)
        tpl["shared_gate"] = ParamTpl((d, f), ("embed", "mlp"), "normal",
                                      dtype)
        tpl["shared_out"] = ParamTpl((f, d), ("mlp", "embed"), "normal",
                                     dtype)
    return tpl


def route(logits: jax.Array, k: int, norm_topk: bool
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax over the experts, top-``k``: ``(probs, gates, experts)``;
    the gates renormalised to sum to one when ``norm_topk``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, experts


def sort_pairs(experts: jax.Array, valid: jax.Array, n_experts: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``experts`` (N, K) per token, ``valid`` (N,).  Returns the pair
    order sorted by expert with invalid tokens' pairs last (stable, so
    within an expert pairs keep token order), its inverse, and the
    number of valid pairs per expert (G,)."""
    N, K = experts.shape
    key = jnp.where(valid[:, None], experts, n_experts).reshape(N * K)
    order = jnp.argsort(key, stable=True)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * K, dtype=order.dtype))
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[key].add(1)
    return order, inv, sizes[:n_experts]


def _mixture(p, xf: jax.Array, gates: jax.Array, experts: jax.Array,
             ok: jax.Array, cfg, layer) -> Tuple[jax.Array, jax.Array]:
    """The top-k mixture of the experts held in ``p`` for ``xf`` (N, D):
    ``(out, sizes)``, ``out`` (N, D) float32 and ``sizes`` the real pairs
    per expert.  ``experts`` (N, K) index ``p``'s stacks; a pair whose
    index is the stacks' expert count, or whose token is not ``ok``, is
    taken by no group and adds nothing."""
    N, D = xf.shape
    K = experts.shape[1]
    # ---- dispatch: sort the pairs by expert ----------------------------
    order, inv, sizes = sort_pairs(experts, ok, p["w_in"].shape[-3])
    P = N * K
    M = padded_rows(P)
    plan = make_plan(sizes, M, cfg.attn_impl)
    tok = order // K                                          # (P,)
    real = jnp.arange(P) < sizes.sum()
    lhs = jnp.where(real[:, None], xf[tok], 0)
    lhs = jnp.pad(lhs, ((0, M - P), (0, 0)))

    # ---- expert GEMMs over the groups ------------------------------------
    h = grouped_matmul(lhs, p["w_in"], plan, layer=layer)
    if "w_gate" in p:
        h = _act(grouped_matmul(lhs, p["w_gate"], plan, layer=layer),
                 cfg.act) * h
    else:
        h = _act(h, cfg.act)
    y = grouped_matmul(h, p["w_out"], plan, out_dtype=jnp.float32,
                       layer=layer)[:P]
    y = jnp.where(real[:, None], y, 0.0)

    # ---- combine: back to token order, weighted by the gates ------------
    y = y[inv].reshape(N, K, D)
    return jnp.sum(y * gates[..., None], axis=1), sizes


def _axes(entry) -> Tuple[str, ...]:
    """The mesh axes of one PartitionSpec entry."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _mixture_sharded(ctx, p, x: jax.Array, gates: jax.Array,
                     experts: jax.Array, ok: jax.Array, cfg, layer
                     ) -> jax.Array:
    """:func:`_mixture` on a mesh, as expert parallelism: each device
    routes the tokens of its batch shard to the experts (and the slice of
    the expert width) it holds, sorting only its own pairs, and the
    partial outputs are summed over the devices that share those tokens.
    Where the expert axis takes a mesh axis that also shards the batch
    (``rules_variant("moe_tp")``), the tokens are first gathered over it
    and the sums scattered back.  ``x`` (B, T, D), ``gates``/``experts``
    (B, T, K), ``ok`` (B, T) → (B, T, D) float32."""
    B, T, D = x.shape
    E = cfg.n_experts
    stacked = p["w_in"].ndim == 4
    lead = (None,) if stacked else ()
    w_spec = ctx.spec(("experts", "moe_embed", "mlp"), p["w_in"].shape[-3:])
    w_spec = tuple(w_spec) + (None,) * (3 - len(w_spec))
    e_axes, f_axes = _axes(w_spec[0]), _axes(w_spec[2])
    b_spec = ctx.spec(("batch",), (B,))
    tok = PartitionSpec(b_spec[0] if len(b_spec) else None)
    b_axes = _axes(tok[0])
    gather = tuple(a for a in b_axes if a in e_axes + f_axes)
    reduce = tuple(a for a in e_axes + f_axes if a not in gather)
    mesh = dict(ctx.mesh.shape)
    n_e = math.prod(mesh[a] for a in e_axes)
    ws = {"w_in": PartitionSpec(*lead, w_spec[0], None, w_spec[2]),
          "w_gate": PartitionSpec(*lead, w_spec[0], None, w_spec[2]),
          "w_out": PartitionSpec(*lead, w_spec[0], w_spec[2], None)}
    w = {k: p[k] for k in EXPERT_STACKS if k in p}

    def local(x, gates, experts, ok, w, layer):
        if gather:
            x, gates, experts, ok = (
                jax.lax.all_gather(a, gather, axis=0, tiled=True)
                for a in (x, gates, experts, ok))
        n = x.shape[0] * T
        e_local = E // n_e
        e0 = jax.lax.axis_index(e_axes) * e_local if e_axes else 0
        mine = experts - e0
        mine = jnp.where((mine >= 0) & (mine < e_local), mine, e_local)
        out, _ = _mixture(w, x.reshape(n, D), gates.reshape(n, -1),
                          mine.reshape(n, -1), ok.reshape(n), cfg, layer)
        out = out.reshape(x.shape)
        if reduce:
            out = jax.lax.psum(out, reduce)
        if gather:
            out = jax.lax.psum_scatter(out, gather, scatter_dimension=0,
                                       tiled=True)
        return out

    return jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(tok, tok, tok, tok, {k: ws[k] for k in w},
                  PartitionSpec()),
        out_specs=tok, check_vma=False)(
            x, gates, experts, ok, w,
            jnp.int32(0) if layer is None else layer)


def moe_ffn(p, x: jax.Array, cfg, valid: Optional[jax.Array] = None, *,
            aux_loss: bool = True, layer: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, T, D), valid: (B, T) bool (None: every token is real) →
    ``(out, aux, counts)``: the load-balancing loss term and the int32
    ``[pairs, experts hit]`` of the real tokens.  With ``layer`` (an int32
    scalar) the expert stacks of ``p`` hold every repeat of the layer's
    group along a leading axis and ``layer`` is this one's.  Under a
    sharding context with a mesh the experts run as expert parallelism
    (:func:`_mixture_sharded`)."""
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * T
    xf = x.reshape(N, D)
    ok = jnp.ones((N,), bool) if valid is None else valid.reshape(N)

    logits = jnp.dot(xf, p["router"], preferred_element_type=jnp.float32)
    probs, gates, experts = route(logits, K, cfg.norm_topk_prob)

    # load-balance aux loss (Switch) over the real tokens: E · Σ_e f_e·p_e
    if aux_loss:
        w = ok.astype(jnp.float32)
        n_ok = jnp.maximum(w.sum(), 1.0)
        me = (probs * w[:, None]).sum(0) / n_ok               # (E,)
        ce = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(
            jnp.repeat(w, K) / (n_ok * K))
        aux = E * jnp.sum(me * ce)
    else:
        aux = jnp.zeros((), jnp.float32)

    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        out, sizes = _mixture(p, xf, gates, experts, ok, cfg, layer)
    else:
        out = _mixture_sharded(
            ctx, p, x, gates.reshape(B, T, K), experts.reshape(B, T, K),
            ok.reshape(B, T), cfg, layer).reshape(N, D)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[
            jnp.where(ok[:, None], experts, E).reshape(-1)].add(1)[:E]
    if "shared_in" in p:
        sh = _act(xf @ p["shared_gate"], cfg.act) * (xf @ p["shared_in"])
        out = out + (sh @ p["shared_out"]).astype(jnp.float32)
    counts = jnp.stack([ok.sum().astype(jnp.int32) * K,
                        (sizes > 0).sum().astype(jnp.int32)])
    return out.reshape(B, T, D).astype(x.dtype), aux, counts


__all__ = ["moe_tpl", "moe_ffn", "route", "sort_pairs", "EXPERT_STACKS"]
