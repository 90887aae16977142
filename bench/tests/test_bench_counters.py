"""The run's counters are the window's change of every counter in the
engine's metrics registry, not of a list the harness names: a run of the
fixture's ``tiny.chat`` on the CPU carries the paged decode kernel's page
counters beside the eight the harness once listed, and no gauge."""

from __future__ import annotations

import json

from conftest import with_fixture
from lib import spec
from repro.serve.engine.engine import COUNTER_METRICS, GAUGE_METRICS

FIRST = ("decode_steps", "decoded_tokens", "prefills", "prefill_tokens",
         "shared_tokens", "prefix_hits", "preemptions", "compiles_total")


def test_every_engine_counter_reaches_the_run(tmp_path, monkeypatch):
    import run
    root = with_fixture(tmp_path)
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "use_cache", lambda: "not used in tests")
    lines = []
    out, _ = run.run_cell(spec.load_cell("tiny.chat", root=root), 2**33 + 5,
                          1.0, False, log=lines.append)
    assert out["correct"], out["checks"]
    said = [l for l in lines if l.startswith("counters over the window ")]
    counters = json.loads(said[0].split(" ", 4)[4].split("; compiles")[0])
    assert set(FIRST) <= set(counters)
    assert set(counters) == set(COUNTER_METRICS)
    assert not set(counters) & set(GAUGE_METRICS)
    assert counters["decode_steps"] > 0 and counters["prefills"] > 0
    assert 0 < counters["decode_pages_live"] <= \
        counters["decode_pages_budget"]
    assert all(isinstance(v, int) and v >= 0 for v in counters.values())
