"""Mellum2 through the normal serving path against its plain reference
(``reference/mellum.py``) on the CPU, at the smoke size: the same pattern
of three window layers and one full layer over two periods, window 16,
8 experts top-2, YaRN over a short original context, float32.  Also the
configuration file against the catalog's entry, and the reference's
check of the program's configuration."""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from lib import spec, weights as W
from repro.configs import get_config, get_smoke_config
from repro.models.layers import Rope
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine

SEED = 2**33 + 29
REF = spec.module(BENCH, "reference", "mellum")
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
# the smoke configuration in the catalog's terms
SMOKE = {
    "name": "mellum2-smoke", "dtype": "float32",
    "hf_config": {
        "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 8, "layer_types": KINDS * 2,
        "mlp_layer_types": ["sparse"] * 8, "sliding_window": 16,
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "vocab_size": 256, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 4,
                "original_max_position_embeddings": 32, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}}
# the catalog's config of Mellum2-12B-A2.5B-Instruct, copied inline
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": KINDS * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types"}


def test_config_file_holds_the_catalog_entry():
    """Every key of the catalog's config, nested groups whole, with its
    value, except the three keys ``reduced`` lists; those hold the first
    eight layers, and ``published`` their published values.  The keys
    stand at the file's top level, where the catalog's numbers are
    compared, and again as ``hf_config``, which the reference reads."""
    f = json.loads((BENCH / "configs" / "mellum2-12b-l8.json").read_text())
    hf = f["hf_config"]
    assert set(hf) == set(CATALOG)
    assert {k: f[k] for k in CATALOG} == hf
    assert set(f["reduced"]) == REDUCED
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert f["published"][key] == value, key
        else:
            assert hf[key] == value and \
                type(hf[key]) is type(value), key
    assert hf["num_hidden_layers"] == 8
    assert hf["layer_types"] == CATALOG["layer_types"][:8]
    assert hf["mlp_layer_types"] == CATALOG["mlp_layer_types"][:8]
    entry = next(c for c in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == "mellum2-12b-l8")
    assert set(entry["reduced"]) == REDUCED
    assert entry["source"] == f["source"]


def test_program_dims_holds_the_program_to_the_file():
    """The program's configuration of the cell passes; one that departs
    in a width, a RoPE number, the window or the layer order does not."""
    f = json.loads((BENCH / "configs" / "mellum2-12b-l8.json").read_text())
    m = f["model"]
    cfg = get_config(m["arch"], **m["overrides"])
    n = REF.program_dims(cfg, f)
    assert n["dh"] == 128 and n["experts"] == 64 and n["top_k"] == 8
    assert n["windows"] == [1024, 1024, 1024, None] * 2
    assert n["moe"] == [True] * 8
    bad = [dataclasses.replace(cfg, head_dim=72),
           dataclasses.replace(cfg, window=512),
           dataclasses.replace(cfg, top_k=4),
           dataclasses.replace(cfg, norm_topk_prob=False),
           dataclasses.replace(cfg, rope_kinds=(("swa", Rope(5e5)),
                                                ("full", Rope(5e5)))),
           dataclasses.replace(cfg, pattern=cfg.pattern[::-1])]
    for b in bad:
        with pytest.raises(ValueError):
            REF.program_dims(b, f)


def _reference_logits(ids):
    """Float32 reference logits at every position of ``ids``."""
    n = REF.dims(SMOKE)
    T = REF.PAD
    row = np.zeros(T, np.int32)
    row[:len(ids)] = ids
    (x,), _, _ = REF.forward_hidden(SMOKE, SEED, [row])
    head = W.draw_one(SEED, REF.LEAVES, "unembed", (n["vocab"], n["d"]))
    ln_f = W.draw_one(SEED, REF.LEAVES, "ln_f", (n["d"],))
    h = REF._rms(x[:len(ids)], ln_f, n["eps"])
    return np.asarray(jnp.dot(h, head.T, precision=REF.HI))


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("mellum2-12b", attn_impl="pallas")
    assert REF.program_dims(cfg, SMOKE)["layers"] == 8
    shapes = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    params = W.make_program_params(shapes, SEED, REF.LEAVES)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), params)


def _serve(cfg, params, prompts, max_new):
    """Serve ``prompts`` together through the paged engine; returns each
    request's (prompt + tokens, logits it was sampled from) and the
    engine.  Admission is first come first served, one prefill at a
    time, and slots are taken lowest first, so the n-th (1, V) call of
    the sampler is the n-th prompt's prefill and a decode call's row s
    belongs to the sequence in slot s."""
    eng = None
    seen = {i: [] for i in range(len(prompts))}
    first = []

    def sample(lg):
        if len(first) < len(prompts) and lg.shape[0] == 1:
            seen[len(first)].append(np.array(lg[0]))
            first.append(True)
        else:
            for slot, seq in eng._slot_seq.items():
                seen[seq.rid].append(np.array(lg[slot]))
        return np.argmax(lg, -1)

    eng = ServeEngine(cfg, params, n_slots=len(prompts), budget=48,
                      paged=True, page_size=4, sample_fn=sample,
                      max_submission_retries=0)
    seqs = [eng.submit(Request(i, list(p), max_new))
            for i, p in enumerate(prompts)]
    while not eng.done:
        eng.step()
    return [(list(p) + s.out_tokens, seen[i])
            for i, (p, s) in enumerate(zip(prompts, seqs))], eng


# Tolerance: both sides compute in float32; the program's matmuls run at
# the CPU's default precision (float32) in another order of reduction than
# the HIGHEST-precision reference (per-expert dense mixture, flash blocks,
# online softmax), so logits agree to rounding: within 1e-5 absolute on logits
# of size ~3.  1e-3 absolute is far below any routing or cache fault (a
# wrong expert, a stale ring slot or a missing YaRN scale each move
# logits by 1e-1 or more at this size).
ATOL = 1e-3


@pytest.mark.parametrize("prompts", ["window_wrap", "one_expert"])
def test_engine_matches_reference(smoke, prompts):
    """Prefill, then paged decode across the window's wrap (positions
    past 16 overwrite the 16-slot ring of the window layers; the first
    prompt is longer than the window, so its prefill already wraps), the
    served logits equal the reference's at every served position.
    ``one_expert``: a prompt of one repeated token gives every prompt
    position the same hidden state, so all of them route to the same two
    experts of every layer — 48 pairs an expert, where a capacity of
    1.25 · T·K/E = 15 would have dropped 33."""
    cfg, params = smoke
    rng = np.random.default_rng(11)
    if prompts == "window_wrap":
        ps = [rng.integers(0, 256, 20), rng.integers(0, 256, 7)]
    else:
        ps = [np.full(24, 17)]
    served, eng = _serve(cfg, params, ps, max_new=14)
    for toks, logits in served:
        L = len(toks) - 14
        ref = _reference_logits(np.asarray(toks[:-1]))
        got = np.stack(logits)
        assert got.shape == (14, 256)
        np.testing.assert_allclose(got, ref[L - 1:], atol=ATOL, rtol=0)
        # greedy serving picked the reference's best where it is clear
        top2 = np.sort(ref[L - 1:], -1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 10 * ATOL
        assert (np.asarray(toks[L:])[clear]
                == ref[L - 1:].argmax(-1)[clear]).all()
    s = eng.stats
    n_prompt = sum(len(p) for p in ps)
    assert s["moe_pairs.prefill"] == n_prompt * 2 * 8
    assert s["moe_pairs.decode"] == 13 * len(ps) * 2 * 8
    if prompts == "one_expert":
        # 24 identical positions, 2 experts each, in each of 8 layers
        assert s["moe_experts_hit.prefill"] == 2 * 8
    assert 0 < s["moe_experts_hit.decode"] <= \
        s["decode_steps"] * 8 * 8



def test_full_width_decode_keeps_its_program_name():
    """The registry's full-width decode, which now also returns the
    routing counts, lowers to a module the decode readers find."""
    import re
    from lib.measure import DECODE_PROGRAMS, PREFILL_PROGRAMS
    from repro.serve import step as S

    cfg = get_smoke_config("mellum2-12b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cache = M.cache_init(cfg, 2, 32)
    lowered = S._cached_decode(cfg, None).lower(
        params, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.full((2,), -1, jnp.int32))
    name = re.search(r"module @(\w+)", lowered.as_text()).group(1)
    assert re.search(DECODE_PROGRAMS, name), name
    assert not re.search(PREFILL_PROGRAMS, name), name


def test_position_readings_give_gaps_and_router_margins():
    """``gaps`` is ``position_readings``'s gaps (the control's too), one
    per served token; each position's margin is its smallest k-th less
    (k+1)-th router logit over the layers, so never negative, and
    ``tools/tie_readings.py`` keeps the positions at or above a
    threshold."""
    import importlib.util
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 256, 40).astype(np.int32), 10),
            (rng.integers(0, 256, 70).astype(np.int32), 30)]
    g, c, m = REF.position_readings(SMOKE, SEED, seqs, control=True)
    sound, ctl = REF.gaps(SMOKE, SEED, seqs, control=True)
    for a, b, x, y, mi, (toks, start) in zip(g, sound, c, ctl, m, seqs):
        assert a.shape == x.shape == mi.shape == (len(toks) - start,)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(x, y)
        assert (mi >= 0).all() and (a >= 0).all()
    loc = BENCH / "tools" / "tie_readings.py"
    spec_ = importlib.util.spec_from_file_location("tie_readings", loc)
    tool = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(tool)
    G, C, Mg = (np.concatenate(a) for a in (g, c, m))
    t = float(np.median(Mg))
    rows = tool.by_tie(G, C, Mg, [0.0, t])
    assert rows["0.0"] == {"kept": 1.0, "gap_max": float(G.max()),
                           "gap_mean": float(G.mean()),
                           "control_gap_max": float(C.max()),
                           "control_gap_mean": float(C.mean())}
    keep = Mg >= t
    assert rows[str(t)]["kept"] == pytest.approx(keep.mean())
    assert rows[str(t)]["gap_max"] == float(G[keep].max())
