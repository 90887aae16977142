"""Operation and byte counts against hand counts at small shapes."""

from __future__ import annotations

import pytest

from roofline import decode_attn, decode_step, flash_attn, prefill_step

DIMS = {"d": 8, "heads": 2, "kv": 1, "dh": 4, "ff": 16, "vocab": 10,
        "layers": 2}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_flash_counts():
    # 4 queries over 1 + 2 + 3 + 4 keys; 2 heads of 4; QK^T and PV
    assert flash_attn.flops(DIMS, 4) == 4 * 2 * 4 * 10
    # after 1 shared token: queries 1..3 over 2 + 3 + 4 keys
    assert flash_attn.flops(DIMS, 4, shared=1) == 4 * 2 * 4 * 9
    # bf16: q and out 2 heads x 4 rows, k and v 1 head x 4 rows, of 4
    assert flash_attn.bytes_moved(DIMS, 4) == 2 * 4 * (2 * 2 * 4 + 2 * 4)
    assert flash_attn.bytes_moved(DIMS, 4, 1) == 2 * 4 * (2 * 2 * 3 + 2 * 4)
    # 320 / 100 flop/s against 192 / 10 B/s: bandwidth bounds
    assert flash_attn.roofline_s(DIMS, PEAK, 4) == pytest.approx(19.2)


def test_decode_attention_counts():
    keys = [3, 5]
    assert decode_attn.flops(DIMS, keys) == 4 * 2 * 4 * 8
    # k, v read over 8 keys; q, out of 2 heads per row; new k, v per row
    assert decode_attn.bytes_moved(DIMS, keys) == \
        2 * 4 * (2 * 8 + 2 * 2 * 2 + 2 * 1 * 2)
    assert decode_attn.roofline_s(DIMS, PEAK, keys) == pytest.approx(22.4)


def test_step_counts():
    # wq 8x8, wk and wv 8x4, wo 8x8, three 8x16 MLP matrices
    assert prefill_step.layer_params(DIMS) == 64 + 64 + 64 + 384
    # 3 unshared tokens through both layers, attention, and one head row
    assert prefill_step.flops(DIMS, 4, shared=1) == \
        2 * (2 * 3 * 576 + 4 * 2 * 4 * 9) + 2 * 8 * 10
    # 2 rows: both layers, attention over 3 + 5 keys, two head rows
    assert decode_step.flops(DIMS, [3, 5]) == \
        2 * (2 * 2 * 576 + 4 * 2 * 4 * 8) + 2 * 2 * 8 * 10
