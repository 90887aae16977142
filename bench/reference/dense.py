"""Plain float32 forward pass of a dense decoder: GQA attention with RoPE
(rotate-half, as in the Hugging Face Llama and Qwen3 code), optional
per-head RMSNorm of q and k before RoPE (Qwen3), a SiLU-gated MLP, RMSNorm
before each block and at the end, tied or separate output head.

It imports nothing of the program under test and draws its own weights
from the seed (``lib/weights.py``), one layer at a time, so that it fits
on the chip beside nothing else.  Every matrix product runs at
``Precision.HIGHEST``: on a TPU a float32 product otherwise runs in
bfloat16 passes.

The module also owns what the harness needs to know of its family:
``LEAVES``, the weight names and kinds ``lib/weights.py`` draws, and
``program_dims``, which holds the program's configuration to the
published sizes.  A configuration of another family names another module
here and brings both of its own.

``control=True`` is the same pass with every weight matrix rounded to
8-bit floats (e4m3) with a scale per output channel: the precision one
step below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights as W

HI = jax.lax.Precision.HIGHEST
# every weight of the family, in the order that picks its random stream
LEAVES = {"embed": "embed", "unembed": "embed", "ln_f": "norm",
          "ln1": "norm", "ln2": "norm", "wq": "matrix", "wk": "matrix",
          "wv": "matrix", "wo": "matrix", "q_norm": "norm",
          "k_norm": "norm", "w_in": "matrix", "w_gate": "matrix",
          "w_out": "matrix"}
PAD = 512          # sequences are padded to a power of two of at least
                   # this, so that few lengths compile
Q_BLOCK = 512      # query rows per attention block
V_BLOCK = 512      # rows per block of output logits


def dims(ref: dict) -> dict:
    """The sizes the pass needs, from the configuration's published
    ``hf_config`` block."""
    h = ref["hf_config"]
    d = h["hidden_size"]
    heads = h["num_attention_heads"]
    return {"d": d, "heads": heads, "kv": h["num_key_value_heads"],
            "dh": h.get("head_dim") or d // heads,
            "ff": h["intermediate_size"], "vocab": h["vocab_size"],
            "layers": h["num_hidden_layers"], "eps": h["rms_norm_eps"],
            "theta": float(h["rope_theta"]),
            "tied": bool(h["tie_word_embeddings"]),
            "qk_norm": bool(ref["reference"]["qk_norm"])}


def program_dims(cfg, ref: dict) -> dict:
    """``dims(ref)``, once the program's configuration ``cfg`` is found to
    hold every one of them and to be a dense model with full attention in
    every layer, in the configuration's ``dtype``; raises ``ValueError``
    where it departs."""
    n = dims(ref)
    got = {"d": cfg.d_model, "heads": cfg.n_heads, "kv": cfg.n_kv_heads,
           "dh": cfg.head_dim, "ff": cfg.d_ff, "vocab": cfg.vocab,
           "layers": cfg.num_layers, "eps": cfg.rms_eps,
           "theta": float(cfg.rope_theta), "tied": cfg.tie_embeddings,
           "qk_norm": cfg.qk_norm}
    diff = {k: (got[k], n[k]) for k in n if got[k] != n[k]}
    if diff or cfg.dtype != ref["dtype"] or \
            tuple(cfg.pattern) != (("full", "dense"),):
        raise ValueError(f"program configuration departs from "
                         f"{ref['name']}: {diff}, dtype {cfg.dtype}, "
                         f"pattern {cfg.pattern}")
    return n


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round to 8-bit floats of 4 exponent and 3 mantissa bits (e4m3),
    with one scale per slice along ``axis``.  ``reduce_precision`` and
    not a cast to ``float8_e4m3fn`` and back: XLA may drop such a pair of
    casts as excess precision, and did on the TPU."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 240.0     # largest e4m3 normal
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, p, n: dict):
    """One block over one sequence ``x`` (T, D)."""
    T = x.shape[0]
    H, K, Dh = n["heads"], n["kv"], n["dh"]
    h = _rms(x, p["ln1"], n["eps"])
    q = jnp.dot(h, p["wq"], precision=HI).reshape(T, H, Dh)
    k = jnp.dot(h, p["wk"], precision=HI).reshape(T, K, Dh)
    v = jnp.dot(h, p["wv"], precision=HI).reshape(T, K, Dh)
    if n["qk_norm"]:
        q = _rms(q, p["q_norm"], n["eps"])
        k = _rms(k, p["k_norm"], n["eps"])
    pos = jnp.arange(T)
    q = _rope(q.transpose(1, 0, 2), pos, n["theta"])        # (H, T, Dh)
    k = _rope(k.transpose(1, 0, 2), pos, n["theta"])        # (K, T, Dh)
    v = v.transpose(1, 0, 2)
    g = H // K
    q = q.reshape(K, g, T, Dh)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[:, :, q0:q0 + Q_BLOCK]
        s = jnp.einsum("kgtd,ksd->kgts", qb, k, precision=HI) * Dh ** -0.5
        causal = pos[None, :] <= (q0 + jnp.arange(qb.shape[2]))[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("kgts,ksd->kgtd", jax.nn.softmax(s, -1), v,
                               precision=HI))
    a = jnp.concatenate(outs, 2).reshape(H, T, Dh).transpose(1, 0, 2)
    x = x + jnp.dot(a.reshape(T, H * Dh), p["wo"], precision=HI)
    h = _rms(x, p["ln2"], n["eps"])
    up = jnp.dot(h, p["w_in"], precision=HI)
    gate = jnp.dot(h, p["w_gate"], precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, p["w_out"], precision=HI)


def layer_shapes(n: dict) -> Dict[str, Tuple[int, ...]]:
    d, qd, kd = n["d"], n["heads"] * n["dh"], n["kv"] * n["dh"]
    s = {"ln1": (d,), "ln2": (d,), "wq": (d, qd), "wk": (d, kd),
         "wv": (d, kd), "wo": (qd, d), "w_in": (d, n["ff"]),
         "w_gate": (d, n["ff"]), "w_out": (n["ff"], d)}
    if n["qk_norm"]:
        s.update(q_norm=(n["dh"],), k_norm=(n["dh"],))
    return s


def _quantize_layer(p: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: (_fp8(v, 0) if v.ndim == 2 else v) for k, v in p.items()}


def gaps(ref: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
         control: bool = False) -> List[np.ndarray]:
    """For each ``(tokens, start)``: the reference's logits at positions
    ``start - 1 ..`` predict ``tokens[start:]`` (the served tokens fed
    back as input).  Returns, per sequence, the gap by which each served
    token's reference logit lies below the reference's best.

    With ``control=True`` it returns, beside those, the gaps of the e4m3
    pass's own first choices at the same positions under the float32
    reference: the control put in the program's place."""
    n = dims(ref)
    layer = jax.jit(lambda x, p: _layer(x, p, n))
    quant = jax.jit(_quantize_layer)
    draw = W.layer_fn(seed, LEAVES, layer_shapes(n))
    embed = W.draw_one(seed, LEAVES, "embed", (n["vocab"], n["d"]))
    xs = []
    for toks, _ in seqs:
        T = PAD
        while T < len(toks) - 1:
            T *= 2
        ids = np.zeros(T, np.int32)
        ids[:len(toks) - 1] = toks[:-1]
        xs.append(embed[jnp.asarray(ids)])
    xq = list(xs)
    for li in range(n["layers"]):
        p = draw(li)
        xs = [layer(x, p) for x in xs]
        if control:
            pq = quant(p)
            xq = [layer(x, pq) for x in xq]
        del p
    head = embed if n["tied"] else \
        W.draw_one(seed, LEAVES, "unembed", (n["vocab"], n["d"]))
    del embed
    ln_f = W.draw_one(seed, LEAVES, "ln_f", (n["d"],))

    @jax.jit
    def logits(x, s0, w):        # V_BLOCK rows from s0: one shape per T
        rows = jax.lax.dynamic_slice_in_dim(x, s0, V_BLOCK)
        return jnp.dot(_rms(rows, ln_f, n["eps"]), w.T, precision=HI)
    head_q = jax.jit(lambda w: _fp8(w, 1))(head) if control else None
    out, ctl = [], []
    for (toks, start), x, x8 in zip(seqs, xs, xq):
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
    return (out, ctl) if control else out
