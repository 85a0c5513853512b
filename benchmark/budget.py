"""One wall-clock budget for a whole run.

The driver stops a run at 360 s (PERF_LEDGER.jsonl, PR 23: `run_timed_out`).
Set-up, warm-up, window, drain and trace reduction must all end inside two
thirds of that; the last third is the margin for a slower host than the ones
this was measured on. Nothing in the harness waits without asking the budget
how long it may."""

from __future__ import annotations

import time

DRIVER_LIMIT_S = 360.0
RUN_BUDGET_S = DRIVER_LIMIT_S * 2.0 / 3.0


class OverBudget(Exception):
    pass


class Budget:
    def __init__(self, total_s: float = RUN_BUDGET_S, start: float | None = None) -> None:
        self.total_s = total_s
        self.start = time.monotonic() if start is None else start

    def used(self) -> float:
        return time.monotonic() - self.start

    def left(self, reserve_s: float = 0.0) -> float:
        """Seconds left after keeping `reserve_s` back for later steps."""
        return self.total_s - self.used() - reserve_s

    def need(self, seconds: float, what: str) -> None:
        if self.left() < seconds:
            raise OverBudget(
                f"{what} needs {seconds:.0f} s and {self.left():.0f} s of the "
                f"run's {self.total_s:.0f} s budget are left")

    def wait_s(self, what: str, at_most: float = 1e9, reserve_s: float = 0.0) -> float:
        """A timeout for one wait: what is left (less the reserve), capped."""
        left = self.left(reserve_s)
        if left <= 0:
            raise OverBudget(f"no time left for {what}: {self.used():.0f} s of "
                             f"{self.total_s:.0f} s used")
        return min(left, at_most)
