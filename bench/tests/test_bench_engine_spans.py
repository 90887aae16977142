"""The engine's ``serve.*`` host spans as the benchmark reads them: the
device's idle time inside them by hand on a synthetic trace, and from a
trace the profiler writes on the CPU; and the numbers the existing
readers take from the recorded trace, pinned."""

from __future__ import annotations

import time

import pytest

from conftest import BENCH
from lib import devtrace, enginespans
from lib.measure import (DECODE_KERNEL, DECODE_PROGRAMS, FLASH_KERNEL,
                         PREFILL_PROGRAMS, Run)
from tools import phase_idle

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, t0, t1):
    return {"plane": plane, "line": line, "name": name, "t0": t0,
            "dur": t1 - t0}


# the engine's ticks: one starts before the window, one runs past it; the
# gap [10, 30) has its midpoint under ``serve.sample`` but overlaps that
# span for 4 ns only, and the gap [40, 70) overlaps ``serve.step`` for
# 5 ns though its midpoint lies under ``bench.sleep``
ENGINE = [
    ev(HOST, "bench", "bench.window", 0, 100),
    ev(HOST, "python", "serve.step", -10, 3),
    ev(HOST, "python", "serve.step", 3, 25),
    ev(HOST, "python", "serve.decode", 3, 5),
    ev(HOST, "python", "serve.logits_to_host", 5, 18),
    ev(HOST, "python", "serve.sample", 18, 22),
    ev(HOST, "python", "serve.step", 28, 45),
    ev(HOST, "bench", "bench.sleep", 45, 88),
    ev(HOST, "python", "serve.step", 90, 120),
    ev(DEV, "XLA Modules", "jit_decode_packed(3)", 0, 10),
    ev(DEV, "XLA Modules", "jit_decode_packed(3)", 30, 40),
    ev(DEV, "XLA Modules", "jit_decode_packed(3)", 70, 80),
]

# a program without the engine's spans
BENCH_ONLY = [e for e in ENGINE if not e["name"].startswith("serve.")]


def _run(window):
    return Run(dims={}, peak={}, records=[], close=1.0, open_loop=True,
               counters={}, queue_waits_ms=[], window=window)


def test_idle_within_and_per_step_by_hand():
    w = devtrace.window(ENGINE, "bench.window")
    busy = w["modules"]
    spans = {n: [e for e in w["host"] if e["name"] == n]
             for n in ("serve.step", "serve.sample", "serve.logits_to_host",
                       "serve.decode")}
    # steps cover [0, 25), [28, 45), [90, 100): idle 15 + 7 + 10
    assert enginespans.idle_within(busy, spans["serve.step"]) == 32
    assert enginespans.idle_within(busy, spans["serve.sample"]) == 4
    assert enginespans.idle_within(busy, spans["serve.logits_to_host"]) == 8
    assert enginespans.idle_within(busy, spans["serve.decode"]) == 0
    assert enginespans.idle_within(busy, []) == 0
    # the midpoint rule gives the whole of [10, 30) to the sample
    gaps = dict(devtrace.summary(w)["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"serve.sample": 20e-9,
                                  "bench.sleep": 30e-9,
                                  "serve.step": 20e-9})
    # three steps start in the window; the one cut at its start does not
    # count, and spans are clipped to the window before they are read
    assert enginespans.idle_per_step_ms(w, ENGINE) == \
        pytest.approx(32e-6 / 3)
    table = phase_idle.phases(w)
    assert table["serve.step"] == pytest.approx(
        {"n": 4, "span_s": 52e-9, "idle_s": 32e-9})
    assert table["serve.sample"] == pytest.approx(
        {"n": 1, "span_s": 4e-9, "idle_s": 4e-9})
    two = busy + [ev("/device:TPU:1", "XLA Modules", "a", 0, 25)]
    # a second device, busy through [0, 25): idle 17 + 10 there
    assert enginespans.idle_within(two, spans["serve.step"]) == \
        (32 + 27) / 2


def test_silent_without_engine_spans(tmp_path):
    """A program that records no ``serve.step`` span (as before the
    engine had them) reads nothing, and raises nothing."""
    w = devtrace.window(BENCH_ONLY, "bench.window")
    assert enginespans.idle_per_step_ms(w, BENCH_ONLY) is None
    assert enginespans.host_idle_ms(_run(None)) is None
    assert enginespans.host_idle_ms(_run(w), trace_root=tmp_path) is None


def test_host_idle_ms_reads_the_runs_trace(tmp_path):
    """A trace written by the profiler on the CPU, laid out as run.py
    lays it out: ``devtrace`` keeps the window and none of the engine's
    spans; ``host_idle_ms`` finds them in the same file, and reads
    nothing from a trace whose window is not the run's."""
    import jax

    root = tmp_path / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(root / "cell"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                with jax.profiler.TraceAnnotation("serve.step", tick=i):
                    time.sleep(0.002)
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    w = devtrace.window(devtrace.load_xplane(str(root / "cell")),
                        "bench.window")
    assert not any(e["name"].startswith("serve.") for e in w["host"])
    steps = [e for e in enginespans.host_events(
        enginespans.newest_xplane(root)) if e["name"] == "serve.step"]
    assert len(steps) == 3
    assert all(w["t0"] < e["t0"] and e["t0"] + e["dur"] <= w["t1"]
               for e in steps)
    # no device in a CPU trace: the whole of every step is idle
    assert enginespans.host_idle_ms(_run(w), trace_root=root) == \
        pytest.approx(sum(e["dur"] for e in steps) / 1e6 / 3)
    moved = {**w, "t0": w["t0"] - 1}
    assert enginespans.host_idle_ms(_run(moved), trace_root=root) is None
    events = enginespans.load(str(root / "cell"))
    assert [e["name"] for e in events].count("serve.step") == 3


RECORDED = BENCH / "tests" / "data" / "trace_smollm_chat.json"


def test_recorded_trace_numbers_pinned():
    """Every quantity the existing readers take from the recorded trace,
    pinned as constants, so that no change made for the engine's spans
    moves any of them."""
    w = devtrace.window(devtrace.load(str(RECORDED)), "bench.window")
    s = devtrace.summary(w)
    assert s["busy_s"] == pytest.approx(0.056276719, rel=1e-12)
    assert s["window_s"] == pytest.approx(0.06, rel=1e-12)
    assert devtrace.total_ns(w["modules"], DECODE_PROGRAMS) == 41569118
    assert devtrace.total_ns(w["modules"], PREFILL_PROGRAMS) == 4219794
    assert devtrace.total_ns(w["ops"], FLASH_KERNEL) == 1111834
    assert devtrace.total_ns(w["ops"], DECODE_KERNEL) == 17890513
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.step", pytest.approx(0.003723281, rel=1e-12)]]
