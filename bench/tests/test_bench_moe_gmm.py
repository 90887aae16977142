"""The grouped expert matmul's counts (``roofline/moe_gmm.py``) against
hand counts at one decode step and one prefill of Mellum2's cell, its
reader (``metrics/moe_gmm_roofline.py``) on synthetic runs, and the
readers the first cells report unchanged on a run that also carries the
expert counters."""

from __future__ import annotations

import types

import pytest

from conftest import BENCH
from lib import spec
from roofline import moe_gmm

PEAK = spec.peaks()["TPU v5 lite"]
DIMS = {"d": 2304, "expert_ff": 896}
LAYERS = 8


def test_decode_step_by_hand():
    """32 rows, top-8, in 8 layers: 2,048 pairs; 63 of 64 experts hit in
    each layer: 504 expert matrices triples read."""
    pairs, hit = 32 * 8 * LAYERS, 63 * LAYERS
    assert moe_gmm.flops(DIMS, pairs) == 2 * 3 * 2304 * 896 * 2048
    weights = 3 * 2304 * 896 * 2 * 504
    acts = 2048 * (2 * 2 * (2304 + 896) + 2 * 896 + 4 * 2304)
    assert moe_gmm.bytes_moved(DIMS, pairs, hit) == weights + acts
    # far below the ridge: bound by the weights' bytes
    t = moe_gmm.roofline_s(DIMS, PEAK, pairs, hit)
    assert t == (weights + acts) / 819e9
    assert 7.5e-3 < t < 8.0e-3


def test_prefill_by_hand():
    """One 1,000-token prompt: 8,000 pairs a layer over all 64 experts,
    125 an expert: 125 operations a weight byte, still below the chip's
    ridge of 197e12 / 819e9 = 240, so bound by the weights' bytes; four
    such prompts in one call would be bound by compute."""
    pairs, hit = 1000 * 8 * LAYERS, 64 * LAYERS
    flops = 6.0 * 2304 * 896 * 64000
    by = 3 * 2304 * 896 * 2 * 512 + 64000 * (4 * 3200 + 2 * 896 + 4 * 2304)
    assert moe_gmm.flops(DIMS, pairs) == flops
    assert moe_gmm.bytes_moved(DIMS, pairs, hit) == by
    assert moe_gmm.roofline_s(DIMS, PEAK, pairs, hit) == by / 819e9
    assert moe_gmm.roofline_s(DIMS, PEAK, 4 * pairs, hit) == \
        4 * flops / 197e12


def _run(counters, kernel_s, window=True):
    return types.SimpleNamespace(
        dims=DIMS, peak=PEAK, counters=counters,
        window={} if window else None,
        kernel_s=lambda pattern: kernel_s
        if pattern == r"^%_moe_gmm\b" else 0.0)


def test_reader_on_a_synthetic_run():
    """Ten decode steps and one prefill in 0.2 s of kernel time: the sum
    of each kind's roofline time over the kernel time, in (0, 100]; and
    nothing to read without the kernel, the trace or the counters."""
    read = spec.metric_reader(BENCH, "moe_gmm_roofline")
    counters = {"moe_pairs.decode": 10 * 2048,
                "moe_experts_hit.decode": 10 * 504,
                "moe_pairs.prefill": 64000, "moe_experts_hit.prefill": 512}
    got = read(_run(counters, 0.2))
    want = 100 * (moe_gmm.roofline_s(DIMS, PEAK, 20480, 5040)
                  + moe_gmm.roofline_s(DIMS, PEAK, 64000, 512)) / 0.2
    assert got == pytest.approx(want) and 0 < got <= 100
    assert read(_run(counters, 0.0)) is None
    assert read(_run(counters, 0.2, window=False)) is None
    assert read(_run({}, 0.2)) == 0.0


def test_first_cells_read_as_before_with_expert_counters(monkeypatch):
    """The expert counters a run of a dense model carries (zeros) change
    no reader of the first two cells, and the kernel's reader finds
    nothing in their trace."""
    from lib import enginespans
    from test_bench_readers_pinned import PINNED

    monkeypatch.setattr(enginespans, "newest_xplane", lambda root: None)
    read = spec.metric_reader(BENCH, "moe_gmm_roofline")
    for workload, (make, pinned) in PINNED.items():
        cell, run = make()
        run.counters.update({f"moe_{k}.{s}": 0
                             for k in ("pairs", "experts_hit")
                             for s in ("prefill", "decode")})
        for name, want in pinned.items():
            assert spec.metric_reader(cell.bench_dir, name)(run) == want
        assert read(run) is None, workload
