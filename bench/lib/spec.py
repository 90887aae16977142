"""Find a cell and everything it names, by name, under the benchmark's
root.

``BENCHMARK.json`` lists cells as configuration × traffic mix.  Every
piece a cell needs lives in a file of its own, found from its name:

- ``configs/<config>.json``: the model, the engine, the reference;
- ``reference/<module>.py``: the plain reference of a family of models,
  with its weight names and its check of the program's configuration;
- ``traffic/<mix>.json``: the mix's parameters, read by ``lib/traffic.py``;
- ``arrivals/<kind>.py``: one module per kind of arrival schedule a mix
  can name;
- ``cells/<workload>.json``: the cell's load (rate or backlog) and the
  limits of its correctness check;
- ``metrics/<metric>.py``: one reader per per-layer metric.

A later cell, mix, configuration or metric is therefore new files and new
entries, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import types
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    load: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    chips: int
    bench_dir: pathlib.Path

    def reference(self) -> types.ModuleType:
        """The configuration's reference module."""
        return module(self.bench_dir, "reference",
                      self.config["reference"]["module"])


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, workload: str, end_to_end_names) -> bool:
    """Whether ``metric`` is reported in ``workload``: listed there, or
    (with no list) wherever the end-to-end metric it moves is."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in end_to_end_names


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench_dir: Optional[pathlib.Path] = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read;
    raises ``KeyError`` for a cell the benchmark does not list."""
    bench_dir = bench_dir or root / "bench"
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'};"
                       f" known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(
        name=name,
        config=_read_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        load=_read_json(bench_dir / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer, chips=int(w["chips"]),
        bench_dir=bench_dir)


def module(bench_dir: pathlib.Path, kind: str, name: str
           ) -> types.ModuleType:
    """The module ``<kind>/<name>.py`` under ``bench_dir``, loaded once."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} {name!r}: {path} is missing")
    if path not in _MODULES:
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


_MODULES: Dict[pathlib.Path, types.ModuleType] = {}


def metric_reader(bench_dir: pathlib.Path, name: str) -> Callable:
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return module(bench_dir, "metrics", name).read


def peaks(bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, dict]:
    """The peaks table, keyed by ``device_kind`` as JAX reports it."""
    return _read_json(bench_dir / "roofline" / "peaks.json")["chips"]
