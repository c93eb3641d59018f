"""Counters of a device host loop: steps run, the device programs that
ran them, and the blocking device-to-host reads between programs.

The Lloyd host loops dispatch one step program per iteration and then
read a scalar back to decide whether to go on; the DBSCAN host loop
dispatches one program per stretch of up to ``state_interval``
expansions, each a step.  Each read blocks the host until the device has
caught up, so their count per step and the seconds spent in them say how
much of an item's time is round trips rather than device work.  One
meter belongs to one item's run; the serving layer copies its counters
into the item's span.  Per-program cost is one ``perf_counter_ns`` pair
and a few integer adds.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict


class StepMeter:
    """Counts one item's steps, its device programs and its blocking
    reads."""

    __slots__ = ("steps", "programs", "syncs", "sync_ns")

    def __init__(self) -> None:
        self.steps = 0      # Lloyd iterations, or expansions + degree pass
        self.programs = 0   # device step programs dispatched
        self.syncs = 0      # blocking device-to-host reads
        self.sync_ns = 0    # nanoseconds blocked in them

    def program(self, steps: int = 1) -> None:
        """Count one device program dispatched that ran ``steps`` steps."""
        self.programs += 1
        self.steps += steps

    def read(self, fn: Callable[..., Any], *args: Any, reads: int = 1) -> Any:
        """``fn(*args)``, timed and counted as ``reads`` blocking reads (a
        callable that waits on the device more than once says how often)."""
        t0 = time.perf_counter_ns()
        try:
            return fn(*args)
        finally:
            self.sync_ns += time.perf_counter_ns() - t0
            self.syncs += reads

    @property
    def sync_s(self) -> float:
        return self.sync_ns / 1e9

    def counters(self) -> Dict[str, Any]:
        return {"steps": self.steps, "programs": self.programs,
                "syncs": self.syncs, "sync_s": self.sync_s}
