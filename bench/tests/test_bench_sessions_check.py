"""The correctness check of a cell of sessions over shared system prompts,
on the CPU at the fixture's size (``tiny.sessions``): a sound run is
correct, a run
whose tokens are altered where they are produced is not, and the control
(the reference with float8 weights in the program's place) reads above
the limit on three seeds."""

from __future__ import annotations

import pytest

from conftest import with_fixture
from lib import check, spec


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    import run
    root = with_fixture(tmp_path_factory.mktemp("tree"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "device_info", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
        mp.setattr(run, "use_cache", lambda: "not used in tests")
        yield run, spec.load_cell("tiny.sessions", root=root)


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 7])
def test_sound_run_correct_and_control_fails(sessions, seed):
    run, cell = sessions
    out, seqs = run.run_cell(cell, seed, 4.0, False, log=lambda *a: None)
    limit = cell.load["limits"]["gap_max"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    r = check.readings(cell.reference(), cell.config, seed, seqs)
    assert r["gap_max"] == out["checks"]["gap_max"]["value"] <= limit
    assert r["control_gap_max"] > limit


def test_altered_token_fails(sessions, monkeypatch):
    from lib.driver import Driver
    run, cell = sessions
    window = Driver.run_window

    def broken(self, *a, **kw):
        sample, hit = self.eng._sample, []

        def altered(lg):             # one decode tick, every row
            tok = sample(lg)
            if not hit and lg.shape[0] > 1:
                hit.append(True)
                return (tok + 1) % lg.shape[-1]
            return tok
        self.eng._sample = altered
        return window(self, *a, **kw)
    monkeypatch.setattr(Driver, "run_window", broken)
    out, _ = run.run_cell(cell, 2**33 + 3, 2.0, False, log=lambda *a: None)
    assert out["correct"] is False, out["checks"]
