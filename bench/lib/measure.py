"""What one run measured, in the form the metric readers take.

End-to-end metrics come from the harness's clock, over every request due
in the window.  Per-layer readers (``metrics/<name>.py``) get a
:class:`Run` and return a number, or ``None`` where the run holds nothing
for them to read.

The step programs and kernels are found in the device trace by the names
the program gives them: its jitted step functions and its Pallas kernel
bodies.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from lib import devtrace

# program names in the device trace
PREFILL_PROGRAMS = r"prefill_bucket|prefill_ext_bucket|align_dyn"
DECODE_PROGRAMS = r"decode_packed|decode_step"
FLASH_KERNEL = r"^%_flash_attention\b"
DECODE_KERNEL = r"^%_decode_attention\b"


def p(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


@dataclasses.dataclass
class Run:
    dims: dict
    peak: dict
    records: list                 # driver.Record
    close: float                  # window length, seconds
    open_loop: bool
    counters: Dict[str, float]    # engine counters over the window
    queue_waits_ms: List[float]   # QUEUED spans of the window's requests
    window: Optional[dict] = None # devtrace.window(), traced runs only
    drain_end: float = 0.0

    # -- requests --------------------------------------------------------
    def due_in_window(self) -> list:
        """Open loop: the requests due in the window.  Backlog: those the
        engine admitted (a queued request is not yet due)."""
        if self.open_loop:
            return [r for r in self.records if r.due < self.close
                    and r.status != "pending"]
        return [r for r in self.records
                if r.first is not None or r.status == "failed"]

    def ttfts_ms(self) -> List[float]:
        """Due time to first token; a request with none is charged the
        whole wait until the drain gave up."""
        return [1e3 * ((r.first if r.first is not None else self.drain_end)
                       - r.due) for r in self.due_in_window()]

    def itls_ms(self) -> List[float]:
        out: List[float] = []
        for r in self.records:
            t = [x for x in r.times if 0.0 <= x <= self.close]
            out.extend(1e3 * np.diff(t))
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.records for x in r.times
                   if 0.0 <= x <= self.close)

    # -- work inside the window, for the roofline and MFU counts --------
    def prefills(self) -> List[Tuple[int, int]]:
        """``(prompt length, shared tokens)`` of each admission whose
        first token came inside the window."""
        return [(len(r.prompt), int(r.seq.shared_tokens))
                for r in self.records
                if r.first is not None and 0.0 <= r.first <= self.close]

    def decode_steps(self) -> List[List[int]]:
        """Per decode step in the window, each row's number of valid
        keys: token ``i >= 1`` of a request with a prompt of ``L`` tokens
        attends over ``L + i`` positions.  Rows are grouped by the step
        that produced them (one stamp per step)."""
        steps: Dict[float, List[int]] = {}
        for r in self.records:
            L = len(r.prompt)
            for i, t in enumerate(r.times):
                if i >= 1 and 0.0 <= t <= self.close:
                    steps.setdefault(t, []).append(L + i)
        return list(steps.values())

    def module_s(self, pattern: str) -> float:
        return devtrace.total_ns(self.window["modules"], pattern) / 1e9

    def kernel_s(self, pattern: str) -> float:
        return devtrace.total_ns(self.window["ops"], pattern) / 1e9


def decode_step_ms(run: Run) -> Optional[float]:
    """Device time of the decode programs per decode step."""
    n = run.counters.get("decode_steps", 0)
    if run.window is None or not n:
        return None
    t = run.module_s(DECODE_PROGRAMS)
    return 1e3 * t / n if t else None


def decode_pages_share(run: Run) -> Optional[float]:
    """How full the paged decode walk's budget is over the window, in
    percent: the engine's ``decode_pages_live`` (each row's pages up to
    its position) over ``decode_pages_budget`` (the step's rows × the
    pages a row's table holds), summed over decode steps.  The host works
    both out from the step's position vector, so the traffic's context
    lengths and the decode width buckets set the share; it says nothing
    of what the kernel does with the grid cells past a row's live
    pages."""
    budget = run.counters.get("decode_pages_budget", 0)
    if not budget:
        return None
    return 100.0 * run.counters.get("decode_pages_live", 0) / budget


def idle_share(run: Run) -> Optional[float]:
    """1 - busy / window, in percent."""
    if run.window is None:
        return None
    busy = run.window["modules"] or run.window["ops"]
    if not busy:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(busy) / 1e9
                    / run.window["window_s"])
