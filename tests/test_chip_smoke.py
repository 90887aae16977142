"""``chip_smoke.py`` on the CPU: its serve-and-compare body at smoke size
(Pallas kernels in interpret mode), and ``main()`` refusing to run without
a TPU.  Also: importing the package initialises no JAX backend, so the
interpret switch and device checks are decided where they are used."""

import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import get_smoke_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_and_compare_smoke_size(chip_smoke, dtype):
    cfg = dataclasses.replace(get_smoke_config("smollm-360m"), dtype=dtype)
    res = chip_smoke.serve_and_compare(
        cfg, n_slots=2, budget=32, page_size=8, n_requests=3,
        prompt_lens=(4, 12), new_tokens=4)
    assert res["requests"] == 3
    assert res["tokens"] == 3 * 4
    errs = res["kernel_errors"]
    assert {"decode_dense", "decode_paged"} <= set(errs)
    assert {"flash_T8", "flash_T16", "flash_T32"} <= set(errs)
    assert all(ratio <= 1.0 for _, ratio in errs.values())


def test_main_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert out == ""                       # no result line


def test_import_initialises_no_backend():
    code = ("import importlib, pkgutil, repro\n"
            "import jax._src.xla_bridge as xb\n"
            "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
            "    importlib.import_module(m.name)\n"
            "print(json.dumps(sorted(xb._backends)))\n")
    r = subprocess.run(
        [sys.executable, "-c", "import json\n" + code],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip().splitlines()[-1]) == []
