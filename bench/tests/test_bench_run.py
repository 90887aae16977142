"""The harness end to end on the CPU: no chip means no result; a cell added
by new files only is found by name; a sound run is correct and a run
whose served tokens are altered where they are produced is not."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, with_fixture
from lib import spec


def _run(cwd, workload="smollm-360m.chat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**32 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_no_chip_no_result():
    proc = _run(ROOT)
    assert _no_result(proc), proc.stdout
    assert "needs 1 of a kind in roofline/peaks.json" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run(tmp_path)
    assert _no_result(proc), proc.stdout


def test_new_cell_found_by_name(fixture_root):
    cell = spec.load_cell("tiny.sessions", root=fixture_root)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["system_prompts"]["count"] == 4
    assert cell.load["rate_per_s"] == 2.0
    assert [m["name"] for m in cell.end_to_end] == \
        ["ttft_p95_ms", "itl_p95_ms", "ttft_p50_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["queue_wait_p95_ms", "prefix_hit_share",
                     "admitted_tokens"]
    read = spec.metric_reader(cell.bench_dir, "admitted_tokens")
    assert read(type("R", (), {"counters": {"prefill_tokens": 5,
                                            "shared_tokens": 2}})) == 7.0
    # the benchmark's own cells still load from the same tree
    own = spec.load_cell("smollm-360m.chat", root=fixture_root)
    assert "queue_wait_p95_ms" in [m["name"] for m in own.per_layer]
    with pytest.raises(KeyError):
        spec.load_cell("tiny.missing", root=fixture_root)


def test_new_arrival_kind_found_by_name(fixture_root):
    """A mix that names an arrival kind added as one new file."""
    from lib import traffic
    cell = spec.load_cell("tiny.bursty", root=fixture_root)
    plan = traffic.make_plan(cell.traffic, cell.load, seed=2**32 + 1,
                             seconds=4.0, vocab=4096, budget=256,
                             bench_dir=cell.bench_dir)
    assert plan.open_loop
    dues = sorted(s.due for s in plan.sessions)
    assert len(dues) == 12
    assert dues == [0.0] * 3 + [1.0] * 3 + [2.0] * 3 + [3.0] * 3
    with pytest.raises(KeyError):
        traffic.make_plan({**cell.traffic, "arrivals": {"kind": "none"}},
                          cell.load, seed=1, seconds=4.0, vocab=4096,
                          budget=256, bench_dir=cell.bench_dir)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """``run`` with its look for a chip passed over, so that the rest of a
    run goes on the CPU, and the fixture's ``tiny.chat`` cell."""
    import run
    root = with_fixture(tmp_path_factory.mktemp("tree"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "device_info", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
        # leave the process's compile cache as the other tests expect it
        mp.setattr(run, "use_cache", lambda: "not used in tests")
        yield run, spec.load_cell("tiny.chat", root=root)


def _alter_one_token(eng):
    """A fault where tokens are produced: the first decode tick after the
    window opens emits each row's next-best token but one."""
    sample, hit = eng._sample, []

    def altered(lg):
        tok = sample(lg)
        if not hit and lg.shape[0] > 1:
            hit.append(True)
            return (tok + 1) % lg.shape[-1]
        return tok
    eng._sample = altered


@pytest.mark.parametrize("fault", [None, "token"])
def test_correct_only_when_sound(tiny, fault, monkeypatch):
    from lib.driver import Driver
    run, cell = tiny
    if fault:
        window = Driver.run_window

        def broken(self, *a, **kw):      # the fault starts with the window
            _alter_one_token(self.eng)
            return window(self, *a, **kw)
        monkeypatch.setattr(Driver, "run_window", broken)
    out, _ = run.run_cell(cell, 2**33 + 1, 1.0, False, log=lambda *a: None)
    json.dumps(out)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] == 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "ttft_p50_ms", "setup_s"}
    assert all(np.isfinite(m["value"]) for m in out["metrics"].values())


@pytest.mark.parametrize("seed", [1, 77, 2**32 + 3])
def test_control_fails_the_limit(tiny, seed):
    """The reference in the program's place, with float8 weights, reads
    above the limit on the prompts and tokens a sound run served."""
    from lib import check
    run, cell = tiny
    out, seqs = run.run_cell(cell, seed, 2.0, False, log=lambda *a: None)
    limit = cell.load["limits"]["gap_max"]
    assert out["checks"]["gap_max"]["value"] <= limit
    r = check.readings(cell.reference(), cell.config, seed, seqs)
    assert r["gap_max"] == out["checks"]["gap_max"]["value"]
    assert r["control_gap_max"] > limit
