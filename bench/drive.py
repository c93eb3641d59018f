"""Open- and closed-loop senders of requests over ``MiningClient.submit``.

Each request's record keeps host-clock times (``time.perf_counter``):
``due`` (open loop: when the schedule says it is sent), ``sent`` (when
``submit`` was called), ``done`` (when the service resolved it, taken in
the completion callback) and the answer.  Latency runs from ``due`` in the
open loop, so a stall also counts against the requests it delayed, and
from ``sent`` in the closed loop.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ANSWER_WAIT_S = 60.0


@dataclass
class Record:
    req: Any                          # loadgen.Request
    due: Optional[float] = None
    sent: Optional[float] = None
    done: Optional[float] = None
    handle: Any = None
    shed: Optional[str] = None        # refused at admission
    error: Optional[str] = None       # admitted, then failed
    result: Optional[Dict[str, Any]] = None

    @property
    def latency(self) -> Optional[float]:
        if self.done is None or self.result is None:
            return None
        start = self.due if self.due is not None else self.sent
        return self.done - start


@dataclass
class Window:
    records: List[Record]
    t0: float
    t1: float
    wall_t0: float
    wall_t1: float
    unanswered: int = 0
    lateness: List[float] = field(default_factory=list)


def _submit(client, rec: Record, shed_types) -> None:
    r = rec.req
    rec.sent = time.perf_counter()
    try:
        h = client.submit(r.tenant, r.algo, r.data, params=r.params)
    except shed_types as e:
        rec.shed = repr(e)
        return
    rec.handle = h

    def _done(_h, rec=rec):
        rec.done = time.perf_counter()

    h.add_done_callback(_done)


def _collect(rec: Record, deadline: float) -> bool:
    """Wait for one answer until ``deadline``; False if it never came."""
    if rec.handle is None:
        return True
    try:
        rec.result = rec.handle.result(max(0.0, deadline - time.perf_counter()))
    except TimeoutError:
        rec.error = "no answer within the wait"
        return False
    except Exception as e:            # the answer is an error
        rec.error = repr(e)
    if rec.done is None:
        rec.done = time.perf_counter()
    return True


def open_loop(client, reqs, seconds: float, shed_types) -> Window:
    """Send each request at its due time from one thread."""
    records = [Record(r) for r in reqs]
    wall_t0 = time.time()
    t0 = time.perf_counter()
    lateness = []
    for rec in records:
        rec.due = t0 + rec.req.due
        wait = rec.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _submit(client, rec, shed_types)
        lateness.append(rec.sent - rec.due)
    t1 = t0 + seconds
    if time.perf_counter() < t1:
        time.sleep(t1 - time.perf_counter())
    wall_t1 = wall_t0 + (t1 - t0)
    deadline = time.perf_counter() + ANSWER_WAIT_S
    missing = sum(not _collect(rec, deadline) for rec in records)
    return Window(records, t0, t1, wall_t0, wall_t1, missing, lateness)


def closed_loop(client, reqs, seconds: float, clients: int,
                shed_types) -> Window:
    """``clients`` threads, each sending its next request once the last one
    is answered, until the window closes."""
    supply = iter(reqs)
    lock = threading.Lock()
    records: List[Record] = []
    exhausted = threading.Event()
    wall_t0 = time.time()
    t0 = time.perf_counter()
    t1 = t0 + seconds

    def client_loop():
        while time.perf_counter() < t1:
            with lock:
                r = next(supply, None)
                if r is None:
                    exhausted.set()
                    return
                rec = Record(r)
                records.append(rec)
            _submit(client, rec, shed_types)
            _collect(rec, time.perf_counter() + ANSWER_WAIT_S)

    threads = [threading.Thread(target=client_loop, name=f"client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if exhausted.is_set():
        raise RuntimeError(
            f"the closed loop used all {len(reqs)} requests made in set-up; "
            f"raise the traffic file's max_rate")
    wall_t1 = wall_t0 + (t1 - t0)
    missing = sum(rec.handle is not None and rec.result is None
                  and rec.error == "no answer within the wait"
                  for rec in records)
    return Window(records, t0, t1, wall_t0, wall_t1, missing)
