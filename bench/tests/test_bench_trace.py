"""The reduction from profiler trace to metrics: by hand on a synthetic
trace, and on a short stretch of a trace recorded on the chip."""

from __future__ import annotations

import pytest

from conftest import BENCH
from lib import devtrace
from lib.measure import (DECODE_KERNEL, DECODE_PROGRAMS, FLASH_KERNEL,
                         PREFILL_PROGRAMS)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, t0, t1):
    return {"plane": plane, "line": line, "name": name, "t0": t0,
            "dur": t1 - t0}


SYNTH = [
    ev(HOST, "bench", "bench.window", 0, 50),
    ev(HOST, "bench", "bench.step", 0, 20),
    ev(HOST, "bench", "bench.sample", 10, 13),
    ev(HOST, "bench", "bench.sleep", 20, 50),
    ev(DEV, "XLA Modules", "jit_decode_step(3)", 0, 10),
    ev(DEV, "XLA Modules", "jit_prefill_bucket(7)", 12, 15),
    ev(DEV, "XLA Modules", "jit_decode_step(3)", 30, 40),
    ev(DEV, "XLA Modules", "jit_decode_step(3)", 48, 60),     # straddles
    ev(DEV, "XLA Modules", "jit_decode_step(3)", 70, 80),     # outside
    ev(DEV, "XLA Ops", "%while.4", 0, 10),                   # container
    ev(DEV, "XLA Ops", "%fusion.1", 0, 4),
    ev(DEV, "XLA Ops", "%fusion.2", 4, 10),
    ev(DEV, "XLA Ops", "%_flash_attention.6", 12, 15),
    ev(DEV, "XLA Ops", "%_decode_attention.8", 30, 35),
]


def test_window_and_summary_by_hand():
    w = devtrace.window(SYNTH, "bench.window")
    assert w["window_s"] == 50e-9
    assert len(w["modules"]) == 4 and w["modules"][-1]["dur"] == 2
    s = devtrace.summary(w)
    assert s["busy_s"] == pytest.approx(25e-9)      # 10 + 3 + 10 + 2
    assert s["window_s"] == pytest.approx(50e-9)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops == pytest.approx({"%fusion": 10e-9,
                                 "%_decode_attention": 5e-9,
                                 "%_flash_attention": 3e-9})
    gaps = dict(s["breakdown"]["idle_gaps"])
    # [10, 12) under sample; [15, 30) and [40, 48) under sleep
    assert gaps == pytest.approx({"bench.sleep": 23e-9,
                                  "bench.sample": 2e-9})
    assert devtrace.total_ns(w["modules"], r"decode_step") == 22
    assert devtrace.total_ns(w["ops"], FLASH_KERNEL) == 3
    assert devtrace.total_ns(w["ops"], DECODE_KERNEL) == 5


def test_busy_averages_over_devices():
    two = [ev(DEV, "XLA Modules", "a", 0, 10),
           ev("/device:TPU:1", "XLA Modules", "a", 0, 30)]
    assert devtrace.busy_ns(two) == 20.0
    assert devtrace.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]


RECORDED = BENCH / "tests" / "data" / "trace_smollm_chat.json"


def test_recorded_trace():
    """A stretch of a traced ``smollm-360m.chat`` run on a TPU v5e: the
    step programs and both kernels are found by the names the metric
    readers use, and the shares lie in their ranges."""
    w = devtrace.window(devtrace.load(str(RECORDED)), "bench.window")
    s = devtrace.summary(w)
    assert 0 < s["busy_s"] < s["window_s"]
    assert devtrace.total_ns(w["modules"], DECODE_PROGRAMS) > 0
    assert devtrace.total_ns(w["ops"], DECODE_KERNEL) > 0
    assert devtrace.total_ns(w["modules"], PREFILL_PROGRAMS) > 0
    assert devtrace.total_ns(w["ops"], FLASH_KERNEL) > 0
    assert s["breakdown"]["device_ops"] and s["breakdown"]["idle_gaps"]
    assert sum(v for _, v in s["breakdown"]["idle_gaps"]) == \
        pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
