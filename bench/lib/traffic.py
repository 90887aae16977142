"""The one traffic generator: a mix's parameters in, a seeded plan out.

A plan is a list of sessions.  A session opens at ``due`` seconds after
the window starts, with an optional shared system prompt, and runs one or
more turns.  Turn ``k + 1``'s prompt is turn ``k``'s prompt, plus its
answer, plus a new user turn, and falls due ``think_s`` after turn ``k``
finished.  A plain request is a session of one turn with no system prompt.

Every seed gets the same sessions and the same gaps between arrivals, in
another order: each quantity is drawn at the stratified quantiles
``(i + 0.5) / n`` of its distribution and paired into sessions by a fixed
shuffle; the seed orders the sessions and the gaps and draws the token
ids.  So runs with different seeds do the same work.

Mix parameters (``traffic/<mix>.json``)::

    arrivals        {"kind": <kind>, ...}: the schedule of the sessions'
                    due times, made by ``arrivals/<kind>.py`` from these
                    parameters and the cell's load (``poisson``: the
                    cell's ``rate_per_s`` over the window; ``backlog``:
                    the cell's ``backlog`` sessions, all due at 0)
    system_prompts  optional {"count", "tokens", "zipf_s"}
    turns           optional {"min", "max"} turns per session (default 1)
    user            a length distribution: the prompt of a one-turn
                    session, the user part of a turn otherwise
    output          the answer length distribution
    think_s         optional {"mean"}: exponential think time
Length distributions: {"dist": "lognormal", "median", "sigma", "min",
"max"} or {"dist": "uniform", "min", "max"}, integers, both ends kept.
A one-turn prompt is clipped to ``budget - output``; a session ends
before a turn would pass ``budget``.
"""

from __future__ import annotations

import dataclasses
import pathlib
import statistics
from typing import List, Optional

import numpy as np

from lib import spec

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Turn:
    user: np.ndarray          # new tokens this turn adds
    max_new: int              # answer length (no stop token)
    think_s: float            # wait after this turn before the next


@dataclasses.dataclass
class Session:
    sid: int
    due: float                # seconds after the window opens
    system: Optional[int]     # index of the shared system prompt
    turns: List[Turn]


@dataclasses.dataclass
class Plan:
    sessions: List[Session]
    system_prompts: List[np.ndarray]
    budget: int
    open_loop: bool           # sessions open over the window, not before

    def first_prompt(self, s: Session) -> np.ndarray:
        head = [] if s.system is None else [self.system_prompts[s.system]]
        return np.concatenate(head + [s.turns[0].user]).astype(np.int32)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(d: dict, n: int) -> np.ndarray:
    q = _quantiles(n)
    if d["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
        v = np.rint(d["median"] * np.exp(d["sigma"] * z))
    elif d["dist"] == "uniform":
        v = d["min"] + np.floor(q * (d["max"] - d["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(v, d["min"], d["max"]).astype(np.int64)


def _exponential(mean: float, n: int) -> np.ndarray:
    return -mean * np.log1p(-_quantiles(n))


def _zipf(count: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), count - 1)


def make_plan(mix: dict, load: dict, *, seed: int, seconds: float,
              vocab: int, budget: int,
              bench_dir: pathlib.Path = spec.BENCH_DIR) -> Plan:
    rng = np.random.default_rng(seed)
    arrivals = spec.module(bench_dir, "arrivals", mix["arrivals"]["kind"])
    dues = arrivals.dues(mix["arrivals"], load, seconds, rng)
    n = len(dues)

    # the sessions' shapes are the same for every seed: a fixed pairing
    # of the stratified draws; the seed orders them and draws the tokens
    fixed = np.random.default_rng(0)
    sp = mix.get("system_prompts")
    systems = [None] * n if sp is None else \
        fixed.permutation(_zipf(sp["count"], sp["zipf_s"], n)).tolist()
    tr = mix.get("turns", {"min": 1, "max": 1})
    n_turns = fixed.permutation(
        _lengths({"dist": "uniform", **tr}, n)).tolist()
    total = int(sum(n_turns))
    users = fixed.permutation(_lengths(mix["user"], total))
    outs = fixed.permutation(_lengths(mix["output"], total))
    think = fixed.permutation(_exponential(mix["think_s"]["mean"], total)) \
        if "think_s" in mix else np.zeros(total)
    system_prompts = [] if sp is None else [
        rng.integers(0, vocab, sp["tokens"]).astype(np.int32)
        for _ in range(sp["count"])]

    shapes, k = [], 0
    for i in range(n):
        length = 0 if systems[i] is None else sp["tokens"]
        turns = []
        for _ in range(n_turns[i]):
            u, o = int(users[k]), int(outs[k])
            if n_turns[i] == 1 and systems[i] is None:
                u = min(u, budget - o)        # one-turn prompt clip
            if length + u + o > budget:
                break
            turns.append((u, o, float(think[k])))
            length += u + o
            k += 1
        k += n_turns[i] - len(turns)
        shapes.append((systems[i], turns))
    sessions = []
    for i, j in enumerate(rng.permutation(n)):
        system, turns = shapes[j]
        if turns:
            sessions.append(Session(i, float(dues[i]), system, [
                Turn(rng.integers(0, vocab, u).astype(np.int32), o, t)
                for u, o, t in turns]))
    return Plan(sessions, system_prompts, budget, arrivals.OPEN_LOOP)


def summary(plan: Plan) -> dict:
    """Counts and length ranges of a plan, for the run's log."""
    first = [len(plan.first_prompt(s)) for s in plan.sessions]
    outs = [t.max_new for s in plan.sessions for t in s.turns]
    return {"sessions": len(plan.sessions),
            "turns": sum(len(s.turns) for s in plan.sessions),
            "first_prompt_min": min(first), "first_prompt_max": max(first),
            "output_min": min(outs), "output_max": max(outs),
            "output_sum": int(sum(outs)),
            "last_due_s": max(s.due for s in plan.sessions)}


__all__ = ["Turn", "Session", "Plan", "make_plan", "summary"]
