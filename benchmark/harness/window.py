"""The measured window and its arithmetic.

A closed loop: one client sends the next history when the last verdict
returns.  The window opens when the first check starts and closes when
the first check to end past `seconds` returns, so every check in it is
whole and a rate is all the work over all the time.
"""

from __future__ import annotations

import statistics
import time
from dataclasses import dataclass
from typing import Callable, Optional


@dataclass
class Check:
    t0: float           # host monotonic clock, s
    t1: float
    ops: int            # operations of the history decided
    entry: int          # pool index
    result: object = None
    error: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Window:
    t0: float
    t1: float
    checks: list
    setup_s: float
    spans: Optional[list] = None      # program spans that ended inside
    counters: Optional[dict] = None   # program counters' growth over it
    trace: Optional[object] = None    # harness.trace.Reduction

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def run_closed_loop(check: Callable[[int], object], n_entries: int,
                    ops_of: Callable[[int], int], seconds: float,
                    around: Optional[Callable] = None) -> tuple:
    """Runs `check(i)` over the pool, in order and round again, until a
    check ends `seconds` or more after the first began.  `around(i)`,
    if given, is a context manager wrapped around each check (a trace
    annotation).  Returns (t0, t1, checks)."""
    checks = []
    t0 = time.monotonic()
    i = 0
    while True:
        e = i % n_entries
        ts = time.monotonic()
        result, error = None, None
        try:
            if around is None:
                result = check(e)
            else:
                with around(e):
                    result = check(e)
        except Exception as ex:  # noqa: BLE001 — a raise is a failed check
            error = f"{type(ex).__name__}: {ex}"
        te = time.monotonic()
        checks.append(Check(ts, te, ops_of(e), e, result, error))
        i += 1
        if te - t0 >= seconds:
            return t0, te, checks


def ops_per_s(w: Window) -> float:
    """Operations of every history decided in the window over the
    window's seconds."""
    return sum(c.ops for c in w.checks if c.error is None) / w.seconds


def percentile(values: list, q: int) -> float:
    """The q-th percentile, linear between order statistics
    (`statistics.quantiles(..., method="inclusive")`)."""
    if len(values) == 1:
        return float(values[0])
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]
