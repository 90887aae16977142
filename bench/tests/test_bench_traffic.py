"""The traffic generator: deterministic from its seed, the same sizes for
every seed, its clips and its prefix structure kept."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import BENCH
from lib import traffic

MIXES = {"chat": ({"rate_per_s": 20.0}, 2048),
         "sessions": ({"rate_per_s": 3.0}, 2048),
         "longgen": ({"backlog": 64}, 3072)}


def plan(mix: str, seed: int, seconds: float = 30.0):
    load, budget = MIXES[mix]
    params = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    return params, traffic.make_plan(params, load, seed=seed,
                                     seconds=seconds, vocab=49152,
                                     budget=budget)


def flat(p):
    return [(s.due, s.system, [(t.user.tolist(), t.max_new, t.think_s)
                               for t in s.turns]) for s in p.sessions]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_plan(mix):
    big = 2**33 + 12345
    assert flat(plan(mix, big)[1]) == flat(plan(mix, big)[1])
    assert flat(plan(mix, big)[1]) != flat(plan(mix, big + 1)[1])


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gets_the_same_sizes(mix):
    a, b = plan(mix, 1)[1], plan(mix, 2**31 + 5)[1]

    def shapes(p):
        return sorted((s.system, [(len(t.user), t.max_new, t.think_s)
                                  for t in s.turns]) for s in p.sessions)
    assert shapes(a) == shapes(b)

    def gaps(p):        # between arrivals, and from the last to the close
        dues = [s.due for s in p.sessions]
        return sorted(np.diff(dues + [max(dues[-1], 30.0)]))
    assert gaps(a) == pytest.approx(gaps(b))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_clips_and_budget(mix):
    params, p = plan(mix, 7)
    budget = MIXES[mix][1]
    for s in p.sessions:
        length = len(p.first_prompt(s)) - len(s.turns[0].user)
        for t in s.turns:
            assert params["output"]["min"] <= t.max_new <= \
                params["output"]["max"]
            assert len(t.user) <= params["user"]["max"]
            assert len(t.user) >= min(params["user"]["min"],
                                      budget - t.max_new)
            length += len(t.user) + t.max_new
            assert length <= budget
        if params["arrivals"]["kind"] == "poisson":
            assert 0.0 <= s.due < 30.0
        else:
            assert s.due == 0.0


def test_open_loop_count_is_rate_times_window():
    _, p = plan("chat", 3, seconds=30.0)
    assert len(p.sessions) == 600
    dues = [s.due for s in p.sessions]
    assert dues == sorted(dues) and dues[0] == 0.0


def test_session_prefix_structure():
    params, p = plan("sessions", 11)
    sp = params["system_prompts"]
    assert len(p.system_prompts) == sp["count"]
    assert all(len(x) == sp["tokens"] for x in p.system_prompts)
    counts = np.bincount([s.system for s in p.sessions],
                         minlength=sp["count"])
    assert counts[0] == counts.max() and counts[0] > counts[-1]  # Zipf
    for s in p.sessions:
        first = p.first_prompt(s)
        assert np.array_equal(first[:sp["tokens"]],
                              p.system_prompts[s.system])
        assert params["turns"]["min"] <= len(s.turns) or \
            len(first) + s.turns[0].max_new > 0
        assert len(s.turns) <= params["turns"]["max"]
