"""Plain float32 forward pass of a Mellum2 decoder (Hugging Face
``model_type: mellum``): GQA attention with rotate-half RoPE chosen by the
layer's kind, sliding-window attention where ``layer_types`` says
``sliding_attention`` (a query sees its last ``sliding_window`` positions,
its own included), YaRN RoPE (``rope_parameters.full_attention``) on the
full-attention layers, and in every layer a mixture of experts: a router
over all ``num_experts``, softmax, the top ``num_experts_per_tok``
renormalised when ``norm_topk_prob``, each a SiLU-gated MLP of
``moe_intermediate_size``.  RMSNorm before attention, before the experts
and at the end; a separate output head.

Departures from the published model, each also in the configuration
file: no q/k norm and no attention bias (the config names neither); the
MTP head of the model card is not computed (the config has no keys for
it); the weights are random, from the seed.  Routing is dropless: every
expert is applied to every position and weighted by its gate, zero for an
expert the position was not routed to, which is the top-k mixture
exactly (and no capacity limit).

It imports nothing of the program under test and draws its own weights
from the seed (``lib/weights.py``), one layer at a time.  Every matrix
product runs at ``Precision.HIGHEST``.  ``control=True`` is the same pass
with every weight matrix (router and expert stacks included) rounded to
e4m3 with a scale per output channel, as in ``dense.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights as W

HI = jax.lax.Precision.HIGHEST
LEAVES = {"embed": "embed", "unembed": "embed", "ln_f": "norm",
          "ln1": "norm", "ln2": "norm", "wq": "matrix", "wk": "matrix",
          "wv": "matrix", "wo": "matrix", "router": "matrix",
          "w_in": "matrix", "w_gate": "matrix", "w_out": "matrix"}
PAD = 512          # sequences are padded to a power of two of at least
                   # this, so that few lengths compile
Q_BLOCK = 512      # query rows per attention block
V_BLOCK = 512      # rows per block of output logits
WINDOW_KIND = "sliding_attention"
FULL_KIND = "full_attention"
MIXER = {WINDOW_KIND: "swa", FULL_KIND: "full"}   # the program's names


def dims(ref: dict) -> dict:
    """The sizes the pass needs, from the configuration's ``hf_config``,
    with the keys of ``roofline/__init__.py`` for a model of mixed
    layers (``windows``, ``moe``, ``experts``, ``top_k``,
    ``expert_ff``)."""
    h = ref["hf_config"]
    kinds = list(h["layer_types"])
    return {"d": h["hidden_size"], "heads": h["num_attention_heads"],
            "kv": h["num_key_value_heads"], "dh": h["head_dim"],
            "ff": h["intermediate_size"], "vocab": h["vocab_size"],
            "layers": h["num_hidden_layers"], "eps": h["rms_norm_eps"],
            "tied": bool(h["tie_word_embeddings"]),
            "kinds": kinds,
            "windows": [h["sliding_window"] if k == WINDOW_KIND else None
                        for k in kinds],
            "moe": [t == "sparse" for t in h["mlp_layer_types"]],
            "experts": h["num_experts"], "top_k": h["num_experts_per_tok"],
            "expert_ff": h["moe_intermediate_size"],
            "norm_topk": bool(h["norm_topk_prob"]),
            "rope": {k: dict(v) for k, v in h["rope_parameters"].items()}}


def _program_rope(spec) -> dict:
    """A program RoPE spec in ``rope_parameters`` terms."""
    if spec.factor == 1.0:
        return {"rope_type": "default", "rope_theta": spec.theta}
    return {"rope_type": "yarn", "rope_theta": spec.theta,
            "factor": spec.factor,
            "original_max_position_embeddings": spec.original_len,
            "beta_fast": spec.beta_fast, "beta_slow": spec.beta_slow,
            "attention_factor": spec.attention_factor}


def program_dims(cfg, ref: dict) -> dict:
    """``dims(ref)``, once the program's configuration ``cfg`` is found to
    hold every one of them: widths, experts and routing, the window, the
    layer kinds in order and each kind's RoPE numbers, in the
    configuration's ``dtype``; raises ``ValueError`` where it departs."""
    n = dims(ref)
    layers = [(m, f) for pattern, count in cfg.groups
              for _ in range(count) for m, f in pattern]
    got = {"d": cfg.d_model, "heads": cfg.n_heads, "kv": cfg.n_kv_heads,
           "dh": cfg.head_dim, "vocab": cfg.vocab,
           "layers": cfg.num_layers, "eps": cfg.rms_eps,
           "tied": cfg.tie_embeddings, "experts": cfg.n_experts,
           "top_k": cfg.top_k, "expert_ff": cfg.d_ff,
           "norm_topk": cfg.norm_topk_prob,
           "kinds": [m for m, _ in layers],
           "moe": [f == "moe" for _, f in layers],
           "windows": [cfg.window if m == "swa" else None
                       for m, _ in layers],
           "rope": {k: _program_rope(cfg.rope_for(MIXER[k]))
                    for k in n["rope"]}}
    want = dict(n, kinds=[MIXER.get(k) for k in n["kinds"]])
    diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
    if diff or cfg.dtype != ref["dtype"] or cfg.qk_norm:
        raise ValueError(f"program configuration departs from "
                         f"{ref['name']}: {diff}, dtype {cfg.dtype}, "
                         f"qk_norm {cfg.qk_norm}")
    return n


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round to e4m3 with one scale per slice along ``axis`` (see
    ``dense.py``: ``reduce_precision``, not a pair of casts)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_frequencies(params: dict, dim: int) -> Tuple[np.ndarray, float]:
    """Inverse frequencies and the cos/sin scale of one kind's
    ``rope_parameters``: ``default``, or ``yarn`` as Hugging Face's
    ``_compute_yarn_parameters`` computes it (truncated correction
    range, linear ramp between the dims of ``beta_fast`` and
    ``beta_slow`` rotations over the original context)."""
    base = float(params["rope_theta"])
    inv = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if params["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    assert params["rope_type"] == "yarn", params
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(params["beta_fast"])), 0)
    high = min(math.ceil(corr(params["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp
    inv = inv / factor * (1 - extra) + inv * extra
    return inv.astype(np.float32), float(params["attention_factor"])


def _rope(x, pos, inv, scale):
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, half)
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _experts(h, p, n: dict):
    """Top-k mixture over every expert of one layer, for ``h`` (T, D),
    and each position's router margin: its k-th router logit less its
    (k+1)-th, how far the choice of the last expert is from a tie."""
    logits = jnp.dot(h, p["router"], precision=HI)
    ranked = jax.lax.top_k(logits, n["top_k"] + 1)[0]
    margin = ranked[:, -2] - ranked[:, -1]
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, n["top_k"])
    if n["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)      # (T, E)

    def one(acc, e):
        wg, wi, wo, g = e
        y = jax.nn.silu(jnp.dot(h, wg, precision=HI)) \
            * jnp.dot(h, wi, precision=HI)
        return acc + g[:, None] * jnp.dot(y, wo, precision=HI), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (p["w_gate"], p["w_in"], p["w_out"], gate.T))
    return out, margin


def _layer(x, p, n: dict, window, inv, scale):
    """One block over one sequence ``x`` (T, D): its output and the
    router margin of each position (``_experts``)."""
    T = x.shape[0]
    H, K, Dh = n["heads"], n["kv"], n["dh"]
    h = _rms(x, p["ln1"], n["eps"])
    q = jnp.dot(h, p["wq"], precision=HI).reshape(T, H, Dh)
    k = jnp.dot(h, p["wk"], precision=HI).reshape(T, K, Dh)
    v = jnp.dot(h, p["wv"], precision=HI).reshape(T, K, Dh)
    pos = jnp.arange(T)
    q = _rope(q.transpose(1, 0, 2), pos, inv, scale)        # (H, T, Dh)
    k = _rope(k.transpose(1, 0, 2), pos, inv, scale)        # (K, T, Dh)
    v = v.transpose(1, 0, 2)
    g = H // K
    q = q.reshape(K, g, T, Dh)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[:, :, q0:q0 + Q_BLOCK]
        s = jnp.einsum("kgtd,ksd->kgts", qb, k, precision=HI) * Dh ** -0.5
        qp = (q0 + jnp.arange(qb.shape[2]))[:, None]
        keep = pos[None, :] <= qp
        if window is not None:
            keep &= pos[None, :] > qp - window
        s = jnp.where(keep, s, -jnp.inf)
        outs.append(jnp.einsum("kgts,ksd->kgtd", jax.nn.softmax(s, -1), v,
                               precision=HI))
    a = jnp.concatenate(outs, 2).reshape(H, T, Dh).transpose(1, 0, 2)
    x = x + jnp.dot(a.reshape(T, H * Dh), p["wo"], precision=HI)
    y, margin = _experts(_rms(x, p["ln2"], n["eps"]), p, n)
    return x + y, margin


def layer_shapes(n: dict) -> Dict[str, Tuple[int, ...]]:
    d, qd, kd = n["d"], n["heads"] * n["dh"], n["kv"] * n["dh"]
    E, f = n["experts"], n["expert_ff"]
    return {"ln1": (d,), "ln2": (d,), "wq": (d, qd), "wk": (d, kd),
            "wv": (d, kd), "wo": (qd, d), "router": (d, E),
            "w_in": (E, d, f), "w_gate": (E, d, f), "w_out": (E, f, d)}


def _quantize_layer(p: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """e4m3 with a scale per output channel: the contracted axis is the
    second to last of a matrix and of an expert stack alike."""
    return {k: (_fp8(v, v.ndim - 2) if v.ndim >= 2 else v)
            for k, v in p.items()}


def forward_hidden(ref: dict, seed: int, ids: Sequence[np.ndarray],
                   control: bool = False) -> Tuple[List, List, List]:
    """The final hidden states (before the last norm) of each id
    sequence, with ``control`` those of the e4m3 pass too, and each
    position's smallest router margin over the layers (float32 pass)."""
    n = dims(ref)
    tables = {k: rope_frequencies(v, n["dh"]) for k, v in n["rope"].items()}
    layers = {}
    for kind, window in zip(n["kinds"], n["windows"]):
        if kind not in layers:
            inv, scale = tables[kind]
            layers[kind] = jax.jit(functools.partial(
                _layer, n=n, window=window, inv=inv, scale=scale))
    quant = jax.jit(_quantize_layer)
    draw = W.layer_fn(seed, LEAVES, layer_shapes(n))
    embed = W.draw_one(seed, LEAVES, "embed", (n["vocab"], n["d"]))
    xs = [embed[jnp.asarray(t)] for t in ids]
    del embed
    xq = list(xs)
    margins = [jnp.full((len(t),), jnp.inf) for t in ids]
    for li, kind in enumerate(n["kinds"]):
        p = draw(li)
        outs = [layers[kind](x, p) for x in xs]
        xs = [x for x, _ in outs]
        margins = [jnp.minimum(m, mi) for m, (_, mi) in zip(margins, outs)]
        if control:
            pq = quant(p)
            xq = [layers[kind](x, pq)[0] for x in xq]
            del pq
        del p
    return xs, (xq if control else []), [np.asarray(m) for m in margins]


def position_readings(ref: dict, seed: int,
                      seqs: Sequence[Tuple[np.ndarray, int]],
                      control: bool = False) -> Tuple[List, List, List]:
    """For each ``(tokens, start)``, at positions ``start - 1 ..`` (the
    served tokens fed back as input): the gap by which each served
    token's reference logit lies below the reference's best; with
    ``control`` the gaps of the e4m3 pass's own first choices under the
    float32 reference (see ``dense.gaps``), else ``None``s; and the
    position's smallest router margin over the layers, how near its
    routing is to a tie (``tools/tie_readings.py`` reads gaps by it)."""
    n = dims(ref)
    ids = []
    for toks, _ in seqs:
        T = PAD
        while T < len(toks) - 1:
            T *= 2
        row = np.zeros(T, np.int32)
        row[:len(toks) - 1] = toks[:-1]
        ids.append(row)
    xs, xq, margins = forward_hidden(ref, seed, ids, control)
    if not control:
        xq = [None] * len(xs)
    head = W.draw_one(seed, LEAVES, "embed" if n["tied"] else "unembed",
                      (n["vocab"], n["d"]))
    ln_f = W.draw_one(seed, LEAVES, "ln_f", (n["d"],))

    @jax.jit
    def logits(x, s0, w):        # V_BLOCK rows from s0: one shape per T
        rows = jax.lax.dynamic_slice_in_dim(x, s0, V_BLOCK)
        return jnp.dot(_rms(rows, ln_f, n["eps"]), w.T, precision=HI)
    head_q = jax.jit(lambda w: _fp8(w, 1))(head) if control else None
    out, ctl, mar = [], [], []
    for (toks, start), x, x8, m in zip(seqs, xs, xq, margins):
        served = np.asarray(toks[start:])
        g, c = [], []
        for r0 in range(start - 1, len(toks) - 1, V_BLOCK):
            r1 = min(r0 + V_BLOCK, len(toks) - 1)
            s0 = min(r0, x.shape[0] - V_BLOCK)
            rows = np.arange(r0 - s0, r1 - s0)
            lg = np.asarray(logits(x, s0, head))[rows]
            best, at = lg.max(-1), np.arange(r1 - r0)
            g.append(best - lg[at, served[r0 - start + 1:r1 - start + 1]])
            if control:
                pick = np.asarray(jnp.argmax(logits(x8, s0, head_q), -1)
                                  )[rows]
                c.append(best - lg[at, pick])
        out.append(np.concatenate(g))
        ctl.append(np.concatenate(c) if control else None)
        mar.append(m[start - 1:len(toks) - 1])
    return out, ctl, mar


def gaps(ref: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
         control: bool = False) -> List[np.ndarray]:
    """``position_readings``'s gaps, and with ``control=True`` the
    control's (see ``dense.gaps``)."""
    out, ctl, _ = position_readings(ref, seed, seqs, control)
    return (out, ctl) if control else out
