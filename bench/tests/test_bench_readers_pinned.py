"""The per-layer readers of the first two cells, pinned: every metric of
``smollm-360m.chat`` on the recorded trace with synthetic requests, and
every metric of ``qwen3-8b-l12.longgen`` on a synthetic trace of a full
batch, reads what it read before the counts learnt window and expert
layers.  The constants were read at the parent of that change."""

from __future__ import annotations

import types

import numpy as np
import pytest

from conftest import BENCH
from lib import devtrace, enginespans, spec
from lib.driver import Record
from lib.measure import Run

RECORDED = BENCH / "tests" / "data" / "trace_smollm_chat.json"
KIND = "TPU v5 lite"


def _record(rid, prompt_len, shared, times):
    r = Record(rid, rid, 0, 0.0, np.zeros(prompt_len, np.int32), len(times))
    r.seq = types.SimpleNamespace(shared_tokens=shared,
                                  out_tokens=list(range(len(times))))
    r.times = list(times)
    r.status = "finished"
    return r


def _dims(cell):
    return cell.reference().dims(cell.config)


def chat_run():
    """The recorded 60-ms window, with eleven decode steps and three
    admissions inside it (one after 1,024 shared tokens)."""
    cell = spec.load_cell("smollm-360m.chat")
    steps = [0.004 + 0.005 * k for k in range(11)]
    records = [
        _record(0, 512, 0, steps),
        _record(1, 1100, 1024, steps[3:]),
        _record(2, 300, 0, [-0.02, -0.01] + steps),
        _record(3, 200, 0, steps[9:]),
        _record(4, 64, 0, [0.059, 0.07]),
    ]
    counters = {"decode_steps": 11, "prefills": 3,
                "prefill_tokens": 512 + 76 + 200, "shared_tokens": 1024,
                "decode_pages_live": 300, "decode_pages_budget": 1024}
    w = devtrace.window(devtrace.load(str(RECORDED)), "bench.window")
    return cell, Run(dims=_dims(cell), peak=spec.peaks()[KIND],
                     records=records, close=0.06, open_loop=True,
                     counters=counters,
                     queue_waits_ms=[0.2, 1.5, 3.0, 12.0, 0.1], window=w)


def _ev(plane, line, name, t0, t1):
    return {"plane": plane, "line": line, "name": name, "t0": t0,
            "dur": t1 - t0}


def longgen_run():
    """A 100-ms window of five 18-ms decode steps over 16 rows with
    prompts of 128-1,028 tokens and 100-655 tokens served before it."""
    cell = spec.load_cell("qwen3-8b-l12.longgen")
    dev, host = "/device:TPU:0", "/host:CPU"
    ms = 1_000_000
    events = [_ev(host, "bench", "bench.window", 0, 100 * ms)]
    for k in range(5):
        t = 20 * ms * k
        events += [_ev(dev, "XLA Modules", "jit_decode_packed(3)", t,
                       t + 18 * ms),
                   _ev(dev, "XLA Ops", "%fusion.1", t, t + 15 * ms),
                   _ev(dev, "XLA Ops", "%_decode_attention.7", t + 15 * ms,
                       t + 18 * ms)]
    steps = [0.018 + 0.02 * k for k in range(5)]
    records = []
    for i in range(16):
        before = 100 + 37 * i
        records.append(_record(i, 128 + 60 * i, 0,
                               list(np.linspace(-9.0, -0.01, before))
                               + steps))
    counters = {"decode_steps": 5, "prefills": 0, "prefill_tokens": 0,
                "shared_tokens": 0, "decode_pages_live": 180,
                "decode_pages_budget": 400}
    w = devtrace.window(events, "bench.window")
    return cell, Run(dims=_dims(cell), peak=spec.peaks()[KIND],
                     records=records, close=0.1, open_loop=False,
                     counters=counters, queue_waits_ms=[], window=w)


PINNED = {
    "smollm-360m.chat": (chat_run, {
        "queue_wait_p95_ms": 10.2,
        "prefill_step_ms": 1.4065979999999998,
        "decode_step_ms.serve": 3.7790107272727274,
        "flash_attn_roofline": 19.93590003310879,
        "prefill_mfu": 67.98936091640569,
        "device_idle_share.serve": 6.2054683333333305,
        "host_idle_ms.serve": None,
    }),
    "qwen3-8b-l12.longgen": (longgen_run, {
        "decode_step_ms.batch": 18.0,
        "decode_attn_roofline": 30.80752136752137,
        "decode_mfu": 2.735893282797518,
        "device_idle_share.batch": 10.000000000000009,
        "host_idle_ms.batch": None,
    }),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_existing_readers_read_as_before(workload, monkeypatch):
    # the engine-span readers look for the run's own trace on disk
    monkeypatch.setattr(enginespans, "newest_xplane", lambda root: None)
    make, pinned = PINNED[workload]
    cell, run = make()
    names = [m["name"] for m in cell.per_layer]
    assert set(pinned) <= set(names)
    for name, want in pinned.items():
        got = spec.metric_reader(cell.bench_dir, name)(run)
        assert got == want, name


@pytest.mark.parametrize("workload,metric,share", [
    ("smollm-360m.chat", "decode_pages_share.serve", 100.0 * 300 / 1024),
    ("qwen3-8b-l12.longgen", "decode_pages_share.batch", 100.0 * 180 / 400),
])
def test_decode_pages_share(workload, metric, share):
    """The first reader of a counter that the harness passes through
    without naming it; silent where the engine walked no page."""
    make, _ = PINNED[workload]
    cell, run = make()
    assert metric in [m["name"] for m in cell.per_layer]
    read = spec.metric_reader(cell.bench_dir, metric)
    assert read(run) == share
    run.counters = {k: v for k, v in run.counters.items()
                    if not k.startswith("decode_pages")}
    assert read(run) is None
