"""Counters of a device host loop: step programs dispatched, and the
blocking device-to-host reads between them.

The Lloyd and DBSCAN host loops dispatch one step program per iteration
or expansion and then read a scalar back to decide whether to go on.
Each such read blocks the host until the device has caught up, so their
count per step and the seconds spent in them say how much of an item's
time is round trips rather than device work.  One meter belongs to one
item's run; the serving layer copies its counters into the item's span.
Per-step cost is one ``perf_counter_ns`` pair and two integer adds.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict


class StepMeter:
    """Counts one item's step programs and its blocking reads."""

    __slots__ = ("steps", "syncs", "sync_ns")

    def __init__(self) -> None:
        self.steps = 0      # step programs dispatched
        self.syncs = 0      # blocking device-to-host reads
        self.sync_ns = 0    # nanoseconds blocked in them

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
        return {"steps": self.steps, "syncs": self.syncs,
                "sync_s": self.sync_s}
