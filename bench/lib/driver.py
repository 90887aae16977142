"""Drive a ``ServeEngine`` with a plan on the host's clock.

One thread: submit what is due, take one engine step, stamp the tokens
that step produced, and sleep only when the engine has nothing to do.
Every request is timed from its due time, so a stalled loop shows in the
latency of the requests that waited for it, and the generator's lateness
(submit time minus due time) is recorded beside it.

Open loop: sessions open on the plan's schedule over the window; after
the window closes no new turn is sent, and the loop steps on until every
request due in the window has its first token, for at most
``DRAIN_S`` seconds.  Backlog: every session is submitted before the
window, and the window counts what the engine emits while it lasts.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from lib.traffic import Plan

DRAIN_S = 60.0


@dataclasses.dataclass
class Record:
    rid: int
    sid: int
    turn: int
    due: float                       # window-relative seconds
    prompt: np.ndarray
    max_new: int
    submit: float = float("nan")
    seq: object = None
    times: List[float] = dataclasses.field(default_factory=list)
    status: str = "pending"          # pending|queued|finished|failed

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    @property
    def tokens(self) -> List[int]:
        return list(self.seq.out_tokens) if self.seq is not None else []


class Driver:
    def __init__(self, engine, plan: Plan, *, request_cls, annotate,
                 clock: Callable[[], float] = time.perf_counter,
                 rid_base: int = 0):
        self.eng = engine
        self.plan = plan
        self.Request = request_cls
        self.ann = annotate
        self.clock = clock
        self.records: List[Record] = []
        self._live: Dict[int, Record] = {}
        self._due: list = []           # heap of (due, rid)
        self._pending: Dict[int, Record] = {}
        self._session = {s.sid: s for s in plan.sessions}
        self.t0 = 0.0
        self.steps = 0
        self.rid_base = rid_base       # request ids stay unique per engine

    # -- scheduling ------------------------------------------------------
    def _add(self, sid: int, turn: int, due: float, prompt: np.ndarray):
        s = self._session[sid]
        rec = Record(self.rid_base + len(self.records), sid, turn, due,
                     prompt,
                     s.turns[turn].max_new)
        self.records.append(rec)
        self._pending[rec.rid] = rec
        heapq.heappush(self._due, (due, rec.rid))

    def schedule_all(self) -> None:
        for s in self.plan.sessions:
            self._add(s.sid, 0, s.due, self.plan.first_prompt(s))

    def now(self) -> float:
        return self.clock() - self.t0

    def _submit_due(self, until: float) -> None:
        now = self.now()
        with self.ann("bench.submit"):
            while self._due and self._due[0][0] <= min(now, until):
                _, rid = heapq.heappop(self._due)
                rec = self._pending.pop(rid)
                rec.seq = self.eng.submit(self.Request(
                    rid, rec.prompt.tolist(), rec.max_new))
                rec.submit = self.now()
                rec.status = "queued"
                self._live[rid] = rec

    def _stamp(self, t: float, next_turns: bool) -> None:
        for rid, rec in list(self._live.items()):
            n = len(rec.seq.out_tokens)
            if n > len(rec.times):
                rec.times.extend([t] * (n - len(rec.times)))
            st = rec.seq.status.value
            if st in ("finished", "failed"):
                rec.status = st
                del self._live[rid]
                s = self._session[rec.sid]
                if st == "finished" and next_turns \
                        and rec.turn + 1 < len(s.turns):
                    nxt = s.turns[rec.turn + 1]
                    prompt = np.concatenate(
                        [rec.prompt, np.asarray(rec.tokens, np.int32),
                         nxt.user]).astype(np.int32)
                    self._add(rec.sid, rec.turn + 1,
                              t + s.turns[rec.turn].think_s, prompt)

    def step(self, next_turns: bool = True) -> None:
        with self.ann("bench.step"):
            self.eng.step()
        self.steps += 1
        self._stamp(self.now(), next_turns)

    # -- phases ----------------------------------------------------------
    def fill(self) -> None:
        """Backlog set-up: submit everything and step until every slot
        holds a request that has produced its first token."""
        self.t0 = self.clock()
        self.schedule_all()
        self._submit_due(float("inf"))
        n_slots = self.eng.n_slots
        while sum(1 for r in self._live.values() if r.times) < \
                min(n_slots, len(self.records)):
            self.step(next_turns=False)

    def run_window(self, seconds: float, open_loop: bool) -> float:
        """Measure for ``seconds``; returns the window's length, from its
        start to the end of the step that closed it."""
        if open_loop:
            self.schedule_all()
        start = self.clock()
        for rec in self.records:       # set-up tokens fall before zero
            rec.times = [t - (start - self.t0) for t in rec.times]
        self.t0 = start
        while True:
            now = self.now()
            if now >= seconds:
                return now
            if open_loop:
                self._submit_due(seconds)
            if self._live:
                self.step()
            elif self._due and self._due[0][0] < seconds:
                with self.ann("bench.sleep"):
                    time.sleep(max(0.0, min(self._due[0][0], seconds)
                                   - self.now()))
            elif not open_loop:
                return self.now()         # backlog ran dry
            else:
                with self.ann("bench.sleep"):
                    time.sleep(max(0.0, seconds - self.now()))

    def drain(self, close: float) -> float:
        """After an open-loop window: send what fell due inside it but is
        not sent yet, and step until each request due in the window has
        its first token, or ``DRAIN_S`` has passed.  Returns the time the
        drain ended."""
        while True:
            now = self.now()
            waiting = [r for r in self.records if r.due < close
                       and not r.times and r.status != "failed"]
            if not waiting or now > close + DRAIN_S:
                return now
            self._submit_due(close)
            if self._live:
                self.step(next_turns=False)
            else:
                time.sleep(0.001)
