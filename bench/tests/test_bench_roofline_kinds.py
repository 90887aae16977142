"""Counts for models with more than one kind of layer (``roofline/
__init__.py``'s optional ``dims`` keys) against hand counts, and, for the
dense configurations' dims, against the formulas the counts had before
those keys existed."""

from __future__ import annotations

import types

import pytest

from conftest import BENCH
from lib import spec
from roofline import (decode_attn, decode_step, flash_attn, layer_kinds,
                      prefill_step)

DIMS = {"d": 8, "heads": 2, "kv": 1, "dh": 4, "ff": 16, "vocab": 10,
        "layers": 2}
# layer 0: window 3 and 4 experts (2 a token, 16 wide) beside a shared
# expert 8 wide; layer 1: full attention and the dense MLP
MIXED = {**DIMS, "windows": [3, None], "moe": [True, False], "experts": 4,
         "top_k": 2, "expert_ff": 16, "shared_ff": 8}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_window_counts_by_hand():
    # queries 0..4 over 1, 2, 3, 3, 3 keys; from 2 on: 3 + 3 + 3
    assert flash_attn.keys(5, 0, 3) == 12
    assert flash_attn.keys(5, 2, 3) == 9
    assert flash_attn.keys(2, 0, 3) == flash_attn.keys(2) == 3
    assert flash_attn.flops(DIMS, 5, 2, window=3) == 4 * 2 * 4 * 9
    # the query at 4 alone reads keys 2, 3, 4
    assert flash_attn.bytes_moved(DIMS, 5, 4, window=3) == \
        2 * 4 * (2 * 2 * 1 + 2 * 1 * 3)
    # a window past the prompt reads every key
    assert flash_attn.bytes_moved(DIMS, 5, 2, window=8) == \
        flash_attn.bytes_moved(DIMS, 5, 2)
    # rows at 2, 5 and 9 positions, at most 4 of them each
    assert decode_attn.flops(DIMS, [2, 5, 9], window=4) == 4 * 2 * 4 * 10
    assert decode_attn.bytes_moved(DIMS, [2, 5, 9], window=4) == \
        2 * 4 * (2 * 10 + 2 * 2 * 3 + 2 * 1 * 3)


def test_expert_layer_params_by_hand():
    attn = 64 + 64 + 64                  # wq, wk and wv, wo
    assert prefill_step.layer_params(MIXED) == attn + 3 * 8 * 16
    # router 8 x 4, two experts of three 8 x 16, shared three 8 x 8
    assert prefill_step.layer_params(MIXED, moe=True) == \
        attn + 32 + 2 * 3 * 8 * 16 + 3 * 8 * 8
    no_shared = {k: v for k, v in MIXED.items() if k != "shared_ff"}
    assert prefill_step.layer_params(no_shared, moe=True) == \
        attn + 32 + 2 * 3 * 8 * 16


def test_step_counts_over_kinds_by_hand():
    assert layer_kinds(MIXED) == [(3, True, 1), (None, False, 1)]
    assert layer_kinds(DIMS) == [(None, False, 2)]
    # 4 unshared tokens: layer 0 at 1184 weights over 2 + 3 + 3 + 3 keys,
    # layer 1 at 576 over 2 + 3 + 4 + 5; one head row
    assert prefill_step.flops(MIXED, 5, shared=1) == \
        2 * 4 * 1184 + 32 * 11 + 2 * 4 * 576 + 32 * 14 + 2 * 8 * 10
    # 2 rows at 3 and 5 keys: layer 0 reads 3 + 3 of them
    assert decode_step.flops(MIXED, [3, 5]) == \
        2 * 2 * 1184 + 32 * 6 + 2 * 2 * 576 + 32 * 8 + 2 * 2 * 8 * 10
    with pytest.raises(ValueError):
        layer_kinds({**DIMS, "windows": [None]})


def _run(dims, steps=(), prefills=()):
    return types.SimpleNamespace(
        dims=dims, peak=PEAK, window={},
        kernel_s=lambda pattern: 2.0,
        decode_steps=lambda: [list(k) for k in steps],
        prefills=lambda: list(prefills))


def test_attention_rooflines_per_layer_kind():
    flash = spec.metric_reader(BENCH, "flash_attn_roofline")
    decode = spec.metric_reader(BENCH, "decode_attn_roofline")
    # one prompt of 5 after 2 shared: 9 keys in layer 0, 12 in layer 1;
    # bandwidth bounds both (q, out of 3 rows; k, v of 5 rows)
    ideal = 2 * (2 * 4 * (2 * 2 * 3 + 2 * 5)) / 10.0
    assert flash(_run(MIXED, prefills=[(5, 2)])) == \
        pytest.approx(100.0 * ideal / 2.0)
    run = _run(MIXED, steps=[[3, 5], [4, 6]])
    keys = [3 + 3, 3 + 3, 3 + 5, 4 + 6]           # layer 0, then layer 1
    ideal = sum(2 * 4 * (2 * k + 2 * 2 * 2 + 2 * 2) / 10.0 for k in keys)
    assert decode(run) == pytest.approx(100.0 * ideal / 2.0)


def _first_counts(dims):
    """The counts as they were written before the optional keys."""
    H, Dh, K = dims["heads"], dims["dh"], dims["kv"]

    def flash_flops(L, s):
        return 4.0 * H * Dh * (L * (L + 1) // 2 - s * (s + 1) // 2)

    def flash_bytes(L, s):
        return 2 * Dh * (2 * H * (L - s) + 2 * K * L)

    def dec_flops(keys):
        return 4.0 * H * Dh * float(sum(keys))

    def dec_bytes(keys):
        r = len(keys)
        return 2 * Dh * (2 * K * float(sum(keys)) + 2 * H * r + 2 * K * r)

    d, q, kv = dims["d"], H * Dh, K * Dh
    params = d * q + 2 * d * kv + q * d + 3 * d * dims["ff"]

    def prefill(L, s):
        return dims["layers"] * (2.0 * (L - s) * params + flash_flops(L, s)) \
            + 2.0 * d * dims["vocab"]

    def decode(keys):
        return dims["layers"] * (2.0 * len(keys) * params + dec_flops(keys)) \
            + 2.0 * len(keys) * d * dims["vocab"]
    return flash_flops, flash_bytes, dec_flops, dec_bytes, params, prefill, \
        decode


@pytest.mark.parametrize("config", ["smollm-360m", "qwen3-8b-l12"])
def test_dense_counts_unchanged(config):
    cell = [c for c in ("smollm-360m.chat", "qwen3-8b-l12.longgen")
            if c.startswith(config)][0]
    c = spec.load_cell(cell)
    dims = c.reference().dims(c.config)
    ff, fb, df, db, params, prefill, decode = _first_counts(dims)
    assert prefill_step.layer_params(dims) == params
    for L, s in [(1, 0), (37, 0), (512, 0), (1100, 1024), (2047, 128),
                 (3000, 2999)]:
        assert flash_attn.flops(dims, L, s) == ff(L, s)
        assert flash_attn.bytes_moved(dims, L, s) == fb(L, s)
        assert prefill_step.flops(dims, L, s) == prefill(L, s)
    for keys in [[1], [513, 2047, 7], list(range(100, 3100, 200))]:
        assert decode_attn.flops(dims, keys) == df(keys)
        assert decode_attn.bytes_moved(dims, keys) == db(keys)
        assert decode_step.flops(dims, keys) == decode(keys)
