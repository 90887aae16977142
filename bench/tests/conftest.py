"""Shared set-up of the benchmark's CPU tests: the benchmark's own
modules on the path, and a benchmark tree with the fixture cells added."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURE = BENCH / "tests" / "fixture"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def with_fixture(dest: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark under ``dest`` with the fixture's cells
    added the way a later change adds one: new files beside the old, new
    entries in ``BENCHMARK.json`` (and the new cells' names in the lists
    of the metrics they report).  Returns the copy's root."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    for sub in ("configs", "traffic", "arrivals", "cells", "metrics"):
        for f in (FIXTURE / sub).iterdir():
            target = dest / "bench" / sub / f.name
            assert not target.exists(), f"fixture file {f.name} exists"
            shutil.copy(f, target)
    spec = json.loads((dest / "BENCHMARK.json").read_text())
    add = json.loads((FIXTURE / "entries.json").read_text())
    spec["configs"] += add["configs"]
    spec["workloads"] += add["workloads"]
    spec["per_layer"] += add["per_layer"]
    for key in ("end_to_end", "per_layer"):
        for m in spec[key]:
            m.get("workloads", []).extend(
                add[f"{key}_workloads"].get(m["name"], []))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


@pytest.fixture
def fixture_root(tmp_path):
    return with_fixture(tmp_path)
