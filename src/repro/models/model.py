"""Unified model definition covering the 10 assigned architectures.

A model is a stack of *pattern groups*: each group scans a superblock of
layers (``jax.lax.scan`` over stacked params — keeps HLO small and AOT
compile times tractable at 48 layers × 512 devices).  A superblock is a
tuple of (mixer_kind, ffn_kind) pairs:

    mixer kinds: full | swa | local | chunked | global_nope | ssm | rec
                 | self_cross (decoder self+cross) | cross (cross-only)
                 | bidir (encoder)
    ffn kinds:   dense | moe | none

Examples: dense LMs = (("full","dense"),)×L; mixtral = (("swa","moe"),)×32;
llama4 = (chunked/dense, chunked/moe, chunked/dense, global_nope/moe)×12;
recurrentgemma = (rec, rec, local)×12 + (rec, rec)×1; whisper decoder =
(("self_cross","dense"),)×24 with a separate bidir encoder stack.

Decode carries a cache pytree mirroring the group structure (KV caches,
rolling buffers, SSM/RG-LRU states, precomputed cross K/V).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import shard_activation
from . import attention as A
from . import layers as L
from . import moe as M
from . import rglru as R
from . import ssm as S
from .layers import ParamTpl

Pattern = Tuple[Tuple[str, str], ...]


class Aux(NamedTuple):
    """What a forward pass reports beside the hidden states: the
    load-balancing loss summed over the expert layers, and per expert
    layer (in model order) the int32 counts ``[pairs, experts hit]`` of
    its real tokens' routing (``(0, 2)`` for a model without experts)."""
    loss: jax.Array
    moe: jax.Array


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense|moe|ssm|hybrid|audio|vlm
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: Pattern = (("full", "dense"),)
    act: str = "silu"
    glu: bool = True
    qk_norm: bool = False
    rope_theta: float = 1e4
    # RoPE per attention kind, ((kind, Rope), ...): a kind not listed
    # takes plain RoPE at ``rope_theta``
    rope_kinds: Tuple[Tuple[str, L.Rope], ...] = ()
    rms_eps: float = 1e-6
    norm_plus_one: bool = False
    embed_scale: bool = False
    tie_embeddings: bool = False
    logits_softcap: float = 0.0
    window: Optional[int] = None
    chunk: Optional[int] = None
    # MoE
    n_experts: int = 0
    top_k: int = 0
    norm_topk_prob: bool = True     # renormalise the top-k gates to sum 1
    shared_expert: bool = False
    aux_loss_weight: float = 0.01
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_kernel: int = 4
    # RG-LRU
    lru_width: int = 0
    # encoder (whisper) / vision (llama3.2) context
    encoder_layers: int = 0
    encoder_seq: int = 0
    vis_tokens: int = 0
    # numerics / impl
    dtype: str = "bfloat16"
    # xla | pallas | auto (autotuned): every Pallas kernel of the model,
    # attention and the expert layers' grouped matmuls
    # (``kernels/grouped_matmul``; ``xla`` runs ``lax.ragged_dot``) alike
    attn_impl: str = "xla"
    attn_chunk: int = 256           # KV-chunk of the streaming softmax
    attn_qblocks: int = 1           # >1: static causal chunk skipping
    norm_impl: str = "xla"
    remat: str = "none"             # none | dots | full
    scan_layers: bool = True
    ce_chunk: int = 0               # 0 = plain CE; >0 = chunked CE
    collect_kv: bool = False        # prefill mode: emit per-layer caches
    analysis_unroll: bool = False   # unroll inner scans (exact HLO flops)
    # long-context decode support
    sub_quadratic: bool = False     # True: decode cache is O(window/state)

    # ---- derived -----------------------------------------------------------
    @property
    def groups(self) -> List[Tuple[Pattern, int]]:
        p = len(self.pattern)
        full, rem = divmod(self.num_layers, p)
        out: List[Tuple[Pattern, int]] = []
        if full:
            out.append((self.pattern, full))
        if rem:
            out.append((self.pattern[:rem], 1))
        return out

    @property
    def has_cross(self) -> bool:
        return any(m in ("self_cross", "cross")
                   for m, _ in self.pattern)

    def rope_for(self, kind: str) -> L.Rope:
        return dict(self.rope_kinds).get(kind, L.Rope(self.rope_theta))

    def cache_len(self, mixer: str, seq_len: int) -> int:
        if mixer in ("swa", "local"):
            return min(self.window or seq_len, seq_len)
        if mixer == "chunked":
            return min(self.chunk or seq_len, seq_len)
        return seq_len


# =============================================================== templates ==

def _mixer_tpl(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    dt = cfg.dtype
    if kind == "ssm":
        return S.ssm_tpl(cfg, dt)
    if kind == "rec":
        return R.rglru_tpl(cfg, dt)
    base = A.attn_tpl(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim, dt, cfg.qk_norm)
    if kind == "self_cross":
        return {"self": base,
                "cross": A.cross_attn_tpl(cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim, dt),
                "ln_cross": L.rmsnorm_tpl(cfg.d_model, dt)}
    return base


def _ffn_tpl(cfg: ModelConfig, kind: str) -> Optional[Dict[str, Any]]:
    if kind == "none":
        return None
    if kind == "moe":
        return M.moe_tpl(cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.dtype,
                         cfg.glu, cfg.shared_expert)
    return L.mlp_tpl(cfg.d_model, cfg.d_ff, cfg.glu, cfg.dtype)


def _layer_tpl(cfg: ModelConfig, mixer: str, ffn: str) -> Dict[str, Any]:
    tpl: Dict[str, Any] = {
        "ln1": L.rmsnorm_tpl(cfg.d_model, cfg.dtype),
        "mixer": _mixer_tpl(cfg, mixer),
    }
    f = _ffn_tpl(cfg, ffn)
    if f is not None:
        tpl["ln2"] = L.rmsnorm_tpl(cfg.d_model, cfg.dtype)
        tpl["ffn"] = f
    return tpl


def param_template(cfg: ModelConfig) -> Dict[str, Any]:
    tpl: Dict[str, Any] = {
        "embed": L.embed_tpl(cfg.vocab, cfg.d_model, cfg.dtype),
        "ln_f": L.rmsnorm_tpl(cfg.d_model, cfg.dtype),
        "groups": [],
    }
    if not cfg.tie_embeddings:
        tpl["unembed"] = L.embed_tpl(cfg.vocab, cfg.d_model, cfg.dtype)
    for pattern, count in cfg.groups:
        layers = tuple(_layer_tpl(cfg, m, f) for m, f in pattern)
        tpl["groups"].append(
            {"layers": tuple(L.stack_tpl(l, count) for l in layers)})
    if cfg.encoder_layers:
        enc_layer = _layer_tpl(cfg, "bidir", "dense")
        tpl["encoder"] = {
            "layers": L.stack_tpl(enc_layer, cfg.encoder_layers),
            "ln_f": L.rmsnorm_tpl(cfg.d_model, cfg.dtype),
        }
    return tpl


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    return L.init_tree(param_template(cfg), key)


def param_count(cfg: ModelConfig) -> Tuple[int, int]:
    """(total, active-per-token) parameter counts."""
    tpl = param_template(cfg)
    leaves = jax.tree.leaves(
        tpl, is_leaf=lambda x: isinstance(x, ParamTpl))
    total = sum(math.prod(l.shape) for l in leaves)
    active = total
    if cfg.n_experts and cfg.top_k:
        # experts contribute top_k/E of their weights per token
        expert_params = sum(
            math.prod(l.shape) for l in leaves
            if len(l.shape) >= 3 and cfg.n_experts in l.shape[:2])
        active = total - expert_params + \
            expert_params * cfg.top_k // cfg.n_experts
    return total, active


# ================================================================= forward ==

def _norm(cfg, w, x):
    return L.rmsnorm(x, w, cfg.rms_eps, cfg.norm_plus_one, cfg.norm_impl)


def _apply_layer(cfg: ModelConfig, mixer: str, ffn: str, p, x, positions,
                 ctx, cache, rolling: bool, rep=None):
    """One transformer-ish layer. Returns (x, new_cache, aux, counts):
    ``counts`` the expert layer's int32 ``[pairs, experts hit]``, None
    for a layer without experts.  With ``rep`` the expert stacks of
    ``p`` are whole (see :func:`_split_expert_stacks`) and ``rep`` is
    this layer's repeat in them.

    ``ctx`` is the raw encoder/vision embedding sequence (B, S_ctx, D);
    cross layers project their own K/V from it.
    """
    aux = jnp.zeros((), jnp.float32)
    counts = None
    h = _norm(cfg, p["ln1"], x)
    new_cache = cache
    if mixer == "ssm":
        out, new_cache = S.ssm_block(p["mixer"], h, cfg, cache)
    elif mixer == "rec":
        out, new_cache = R.rglru_block(p["mixer"], h, cfg, cache)
    elif mixer == "bidir":
        out = A.bidir_attention(p["mixer"], h, cfg)
    elif mixer == "cross":
        kv = A.context_kv(p["mixer"], ctx, cfg)
        out = A.cross_attention(p["mixer"], h, kv, cfg)
    elif mixer == "self_cross":
        out, new_cache = A.self_attention(
            p["mixer"]["self"], h, cfg, "full", positions, cache, False)
        x = x + out
        h2 = _norm(cfg, p["mixer"]["ln_cross"], x)
        kv = A.context_kv(p["mixer"]["cross"], ctx, cfg)
        out = A.cross_attention(p["mixer"]["cross"], h2, kv, cfg)
    else:
        out, new_cache = A.self_attention(
            p["mixer"], h, cfg, mixer, positions, cache,
            rolling and mixer in ("swa", "local", "chunked"))
    x = x + out
    if ffn != "none":
        h = _norm(cfg, p["ln2"], x)
        if ffn == "moe":
            # padding positions (-1: bucket tails, idle decode rows) are
            # routed nowhere
            valid = jnp.broadcast_to(positions >= 0, x.shape[:2])
            out, aux, counts = M.moe_ffn(p["ffn"], h, cfg, valid,
                                         layer=rep)
        else:
            out = L.mlp(p["ffn"], h, cfg.act, cfg.glu)
        x = x + out
    x = shard_activation(x, ("batch", "seq_ctx", "embed"))
    return x, new_cache, aux, counts


def apply_superblock(cfg: ModelConfig, pattern: Pattern, x, layer_params,
                     layer_caches, positions, ctx, rolling: bool, rep=None):
    """One superblock (one scan iteration): the unit the dry-run probes.
    Returns ``(x, caches, Aux)``, the expert counts of the pattern's
    expert layers in pattern order.  ``rep``: see :func:`_apply_layer`."""
    aux_tot = jnp.zeros((), jnp.float32)
    new_caches, moe = [], []
    for i, (mixer, ffn) in enumerate(pattern):
        c = layer_caches[i] if layer_caches is not None else None
        x, nc, aux, counts = _apply_layer(cfg, mixer, ffn, layer_params[i],
                                          x, positions, ctx, c, rolling, rep)
        new_caches.append(nc)
        aux_tot = aux_tot + aux
        if counts is not None:
            moe.append(counts)
    return x, tuple(new_caches), Aux(
        aux_tot, jnp.stack(moe) if moe else jnp.zeros((0, 2), jnp.int32))


def remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    return fn


def _split_expert_stacks(pattern: Pattern, layers):
    """A group's stacked layer params without the expert stacks
    (``moe.EXPERT_STACKS``) of its expert layers, and those stacks whole,
    per pattern position (``{}`` for a layer without experts).  The
    stacks are handed to every repeat whole, with the repeat's index:
    the grouped expert kernel reads its layer's experts out of the
    stack, where a slice per repeat would copy every expert matrix of
    the layer before the kernel reads the few a decode step routes to."""
    rest, stacks = [], []
    for (_, ffn), lp in zip(pattern, layers):
        whole = {k: v for k, v in lp["ffn"].items()
                 if k in M.EXPERT_STACKS} if ffn == "moe" else {}
        if whole:
            lp = dict(lp, ffn={k: v for k, v in lp["ffn"].items()
                               if k not in whole})
        rest.append(lp)
        stacks.append(whole)
    return tuple(rest), tuple(stacks)


def _join_expert_stacks(layers, stacks):
    """The inverse of :func:`_split_expert_stacks` on one repeat's
    params: each expert layer's ``ffn`` gets its whole stacks back."""
    return tuple(dict(lp, ffn={**lp["ffn"], **s}) if s else lp
                 for lp, s in zip(layers, stacks))


def _group_forward(cfg: ModelConfig, pattern: Pattern, count: int, gp,
                   x, positions, ctx, caches, rolling: bool):
    """Scan a superblock over ``count`` repeats.

    caches: None (training) or tuple (per pattern position) of stacked cache
    pytrees with leading dim = count.
    """
    superblock = remat_wrap(
        cfg, lambda x, lp, lc, rep: apply_superblock(
            cfg, pattern, x, lp, lc, positions, ctx, rolling, rep))
    layers, stacks = _split_expert_stacks(pattern, gp["layers"])

    emit = caches is not None or cfg.collect_kv
    if not cfg.scan_layers or count == 1:
        aux_tot = jnp.zeros((), jnp.float32)
        new_caches_all, moe = [], []
        for j in range(count):
            lp = jax.tree.map(lambda a: a[j], layers)
            lc = None if caches is None else \
                jax.tree.map(lambda a: a[j], caches)
            x, ncs, aux = superblock(x, _join_expert_stacks(lp, stacks),
                                     lc, jnp.int32(j))
            new_caches_all.append(ncs)
            aux_tot = aux_tot + aux.loss
            moe.append(aux.moe)
        new_stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *new_caches_all) if emit else None
        return x, new_stacked, Aux(aux_tot, jnp.concatenate(moe))

    def body(carry, xs):
        x, aux_tot = carry
        lp, lc, rep = xs
        x, ncs, aux = superblock(x, _join_expert_stacks(lp, stacks), lc,
                                 rep)
        return (x, aux_tot + aux.loss), (ncs, aux.moe)

    (x, aux_tot), (new_caches, moe) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (layers, caches, jnp.arange(count, dtype=jnp.int32)))
    # (count, expert layers of the pattern, 2) → model layer order
    return x, new_caches, Aux(aux_tot, moe.reshape(-1, 2))


def forward(cfg: ModelConfig, params, tokens, *,
            ctx_embed: Optional[jax.Array] = None,
            cache: Optional[Dict] = None,
            pos0: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Optional[Dict], Aux]:
    """Returns (hidden (B,T,D), new_cache, :class:`Aux`).

    Training/prefill: cache=None, positions = arange(T).
    Decode: cache given, tokens (B,1), pos0 the absolute position — a
    scalar (lockstep batch: every sequence at the same depth) or a (B,)
    vector (continuous batching: per-sequence depths; -1 = inactive slot).
    Partial prefill (prefix sharing): cache is a *prefix* cache under
    ``cfg.collect_kv``, tokens (B, T>1) resume the prompt mid-sequence
    and scalar pos0 is the resume offset — positions = pos0 + arange(T).
    An explicit ``positions`` (T,) int32 overrides both derivations —
    the shape-bucketed prefill path passes ``-1`` for right-padding
    positions, which the attention masks treat as never-valid (the same
    sentinel the ring caches use for unwritten slots).
    """
    B, T = tokens.shape
    x = L.embed(params["embed"], tokens, cfg.embed_scale)
    if positions is not None:
        assert positions.ndim == 1, \
            "explicit positions are a (T,) plan shared by the batch"
        positions = jnp.asarray(positions, jnp.int32)
    elif pos0 is None:
        positions = jnp.arange(T)
    else:
        # int32 throughout: positions feed ring indices and the int32
        # validity planes (and must not drift to int64 under x64)
        pos0 = jnp.asarray(pos0, jnp.int32)
        if pos0.ndim == 0:
            # T == 1 decode this is the position itself; T > 1 is the
            # partial-prefill resume: contiguous positions from pos0
            positions = pos0 + jnp.arange(T, dtype=jnp.int32)
        else:       # per-sequence decode depths → (B, T) position plane
            positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]

    ctx = None
    if cfg.has_cross:
        if cache is not None and "ctx_enc" in cache:
            ctx = cache["ctx_enc"]
        else:
            assert ctx_embed is not None, "cross-attn model needs ctx_embed"
            ctx = ctx_embed
            if cfg.encoder_layers:
                ctx = encode(cfg, params, ctx_embed)

    new_cache: Optional[Dict] = None
    if cache is not None:
        new_cache = {k: v for k, v in cache.items() if k != "groups"}
        new_cache["groups"] = list(cache["groups"])
    elif cfg.collect_kv:
        new_cache = {"groups": [None] * len(cfg.groups)}
        if ctx is not None:
            new_cache["ctx_enc"] = ctx
    aux_tot = jnp.zeros((), jnp.float32)
    moe = []
    for gi, (pattern, count) in enumerate(cfg.groups):
        gp = params["groups"][gi]
        gc = None if cache is None else cache["groups"][gi]
        x, ngc, aux = _group_forward(cfg, pattern, count, gp, x, positions,
                                     ctx, gc, rolling=cache is not None)
        aux_tot = aux_tot + aux.loss
        moe.append(aux.moe)
        if new_cache is not None:
            new_cache["groups"][gi] = ngc
    x = _norm(cfg, params["ln_f"], x)
    return x, new_cache, Aux(aux_tot, jnp.concatenate(moe))


def encode(cfg: ModelConfig, params, frames: jax.Array) -> jax.Array:
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend: conv feature extraction is assumed done upstream)."""
    enc = params["encoder"]
    x = frames.astype(jnp.dtype(cfg.dtype))
    T = x.shape[1]
    positions = jnp.arange(T)

    block = remat_wrap(
        cfg, lambda x, lp: _apply_layer(cfg, "bidir", "dense", lp, x,
                                        positions, None, None, False)[0])

    def body(x, lp):
        return block(x, lp), None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return _norm(cfg, enc["ln_f"], x)


def logits_fn(cfg: ModelConfig, params, hidden: jax.Array) -> jax.Array:
    emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed(emb, hidden, cfg.logits_softcap)


def loss_fn(cfg: ModelConfig, params, tokens, labels,
            ctx_embed: Optional[jax.Array] = None) -> jax.Array:
    hidden, _, aux = forward(cfg, params, tokens, ctx_embed=ctx_embed)
    emb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if cfg.ce_chunk:
        ce = L.chunked_cross_entropy(hidden, emb, labels, cfg.ce_chunk,
                                     cfg.logits_softcap)
    else:
        logits = L.unembed(emb, hidden, cfg.logits_softcap)
        ce = L.cross_entropy(logits, labels)
    return ce + cfg.aux_loss_weight * aux.loss


# ================================================================== cache ===

# attention-cache kinds (ring KV caches); "ssm"/"rec" are state caches and
# None marks cache-less positions (cross-only layers)
KV_KINDS = ("full", "swa", "local", "chunked", "global_nope")


def cache_layout(cfg: ModelConfig) -> List[Tuple[Tuple[Optional[str], ...],
                                                 int]]:
    """Cache kind of every (group, pattern-position) cache leaf.

    Mirrors ``cfg.groups``: one ``(kinds, count)`` entry per group, where
    ``kinds[pi]`` is the KV kind (member of :data:`KV_KINDS`, with
    ``self_cross`` folded into ``"full"``), ``"ssm"``/``"rec"`` for state
    caches, or ``None`` for positions that keep no per-step cache.  The
    single source of truth for code that walks cache pytrees structurally
    (``cache_init``, prefill alignment, the paged KV pool).
    """
    out: List[Tuple[Tuple[Optional[str], ...], int]] = []
    for pattern, count in cfg.groups:
        kinds: List[Optional[str]] = []
        for mixer, _ in pattern:
            if mixer == "self_cross":
                kinds.append("full")
            elif mixer in KV_KINDS or mixer in ("ssm", "rec"):
                kinds.append(mixer)
            else:
                kinds.append(None)
        out.append((tuple(kinds), count))
    return out


def cache_init(cfg: ModelConfig, batch: int, seq_len: int,
               ctx_embed: Optional[jax.Array] = None) -> Dict:
    """Build an empty decode cache for a context of ``seq_len``."""
    dt = jnp.dtype(cfg.dtype)
    groups = []
    for kinds, count in cache_layout(cfg):
        pos_caches = []
        for kind in kinds:
            if kind == "ssm":
                c = S.ssm_cache_init(cfg, batch)
            elif kind == "rec":
                c = R.rglru_cache_init(cfg, batch)
            elif kind in KV_KINDS:
                S_len = cfg.cache_len(kind, seq_len)
                c = A.KVCache(
                    k=jnp.zeros((batch, cfg.n_kv_heads, S_len, cfg.head_dim),
                                dt),
                    v=jnp.zeros((batch, cfg.n_kv_heads, S_len, cfg.head_dim),
                                dt),
                    pos=jnp.full((batch, S_len), -1, jnp.int32))
            else:  # cross-only layers keep no per-step cache
                c = None
            # broadcast (not zero-fill) over the layer dim so non-zero
            # initial state (ring positions = -1) survives the stacking
            pos_caches.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (count,) + a.shape), c))
        groups.append(tuple(pos_caches))
    cache: Dict[str, Any] = {"groups": groups}
    return cache


def decode_step(cfg: ModelConfig, params, cache: Dict, token: jax.Array,
                pos: jax.Array, ctx_embed: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict, jax.Array]:
    """One-token decode: token (B,1) int32; pos scalar int32 (lockstep) or
    (B,) int32 per-sequence absolute positions (-1 = inactive slot).
    Returns ``(logits, new_cache, moe)``, ``moe`` the expert layers'
    routing counts (``Aux.moe``)."""
    hidden, new_cache, aux = forward(cfg, params, token,
                                     ctx_embed=ctx_embed, cache=cache,
                                     pos0=pos)
    return logits_fn(cfg, params, hidden), new_cache, aux.moe


__all__ = ["ModelConfig", "Aux", "param_template", "init_params",
           "param_count", "forward", "encode", "loss_fn", "logits_fn",
           "cache_init", "cache_layout", "KV_KINDS", "decode_step"]
