"""The service's spans read against the device trace: each span's self
time, sums per request, device idle time by its cause, and the check that
the spans and the device events lie on one clock.

Spans are the dicts ``ClusteringService.export_trace`` gives
(``service/trace.py``): ``t0`` in epoch seconds on the host's wall clock,
``dur_s``, ``span_id``, ``parent``, ``pid``/``tid`` (the OS thread), and
``attrs``.  The device trace (bench/trace_reduce.py) places its events on
the same wall clock, in nanoseconds.  The profiler maps a TPU's device
events onto that clock once per capture, and not always exactly: on a TPU
v5e some captures read every program up to about 1.3 ms before the host
enqueued it.  Idle time is attributed as the trace reads it, unshifted, and
:func:`check_clock` refuses a run where that puts the step programs outside
the spans that ran them.

Idle time is credited so: at each moment of an idle gap of the first
device, every thread with a span open contributes the innermost of them
(the deepest by ``parent`` links, the latest begun among equals), and the
moment is split equally among the threads.  Waits (``*_wait`` spans) are
states of a request, emitted after the fact on whichever thread noticed
them, so they count only where no thread had a working span open.  What
no span covers is ``no request in flight``.

The metric readers built on this module return nothing where the program
records no ``steps`` or ``host_compute`` span (a program without this
tracing).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import trace_reduce

NO_REQUEST = "no request in flight"
WORK = ("steps", "host_compute")
# the single-device step programs: the jitted masked Lloyd step (XLA or
# fused Pallas), the DBSCAN degree pass and expansion
STEP_PROGRAMS = re.compile(r"masked_kmeans_step|_degree_step|_expand_step")
# below this share of the step programs' device time inside ``steps``
# spans, host spans and device events do not share a clock and no idle
# time can be put down to a span
CLOCK_MIN_SHARE = 0.95

Interval = Tuple[int, int]


class ClockMismatch(RuntimeError):
    """The step programs ran outside the spans that dispatched them."""


def complete(spans: Iterable[dict]) -> List[dict]:
    """Spans that finished and lasted: no journaled starts, no marks."""
    return [s for s in spans if s.get("phase") != "start"
            and s.get("dur_s") is not None and float(s["dur_s"]) > 0.0]


def interval(span: dict) -> Interval:
    s = int(round(float(span["t0"]) * 1e9))
    return s, s + int(round(float(span["dur_s"]) * 1e9))


def traced(spans: Iterable[dict]) -> bool:
    """Does the program record its items' work as spans?"""
    return any(s.get("name") in WORK for s in spans)


def self_ns(spans: Sequence[dict]) -> Dict[str, int]:
    """Each span's duration minus the part of it its children cover."""
    spans = complete(spans)
    children: Dict[str, List[Interval]] = {}
    for sp in spans:
        if sp.get("parent"):
            children.setdefault(sp["parent"], []).append(interval(sp))
    out = {}
    for sp in spans:
        s, e = interval(sp)
        covered = sum(b - a for a, b in trace_reduce.union(
            (max(a, s), min(b, e)) for a, b in children.get(sp["span_id"],
                                                            ())))
        out[sp["span_id"]] = (e - s) - covered
    return out


def per_request(spans: Iterable[dict], names: Sequence[str]
                ) -> Dict[str, float]:
    """Seconds per trace id in the spans of the given names."""
    out: Dict[str, float] = {}
    for sp in complete(spans):
        if sp["name"] in names:
            out[sp["trace_id"]] = out.get(sp["trace_id"], 0.0) + float(
                sp["dur_s"])
    return out


def _depths(spans: Sequence[dict]) -> List[int]:
    by_id = {sp["span_id"]: sp for sp in spans}
    memo: Dict[str, int] = {}

    def depth(sid: str) -> int:
        chain = []
        while sid in by_id and sid not in memo:
            chain.append(sid)
            sid = by_id[sid].get("parent")
        d = memo.get(sid, -1)
        for s in reversed(chain):
            d += 1
            memo[s] = d
        return d

    return [depth(sp["span_id"]) for sp in spans]


def segments(spans: Sequence[dict]) -> List[Tuple[int, int, Dict[str, float]]]:
    """Where any of ``spans`` is open: ``(start, end, {name: share})``,
    each stretch split equally among the threads' innermost spans."""
    depth = _depths(spans)
    events = []
    for i, sp in enumerate(spans):
        s, e = interval(sp)
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    events.sort()
    open_: Dict[tuple, Dict[int, tuple]] = {}
    top: Dict[tuple, str] = {}
    out = []
    k = 0
    while k < len(events):
        t = events[k][0]
        changed = set()
        while k < len(events) and events[k][0] == t:
            _, starts, i = events[k]
            sp = spans[i]
            thread = (sp.get("pid"), sp.get("tid"))
            if starts:
                open_.setdefault(thread, {})[i] = (depth[i], interval(sp)[0],
                                                   i)
            else:
                open_.get(thread, {}).pop(i, None)
            changed.add(thread)
            k += 1
        for thread in changed:
            if open_.get(thread):
                top[thread] = spans[max(open_[thread].values())[2]]["name"]
            else:
                open_.pop(thread, None)
                top.pop(thread, None)
        if k < len(events) and top:
            share = 1.0 / len(top)
            weights: Dict[str, float] = {}
            for name in top.values():
                weights[name] = weights.get(name, 0.0) + share
            out.append((t, events[k][0], weights))
    return out


def _credit(pieces: List[Interval], segs, out: Dict[str, float]
            ) -> List[Interval]:
    """Credit the parts of ``pieces`` that ``segs`` cover; return the rest."""
    starts = [s for s, _, _ in segs]
    rest = []
    for a, b in pieces:
        cursor = a
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(segs) and segs[j][0] < b:
            s, e, weights = segs[j]
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                if lo > cursor:
                    rest.append((cursor, lo))
                for name, w in weights.items():
                    out[name] = out.get(name, 0.0) + w * (hi - lo)
                cursor = max(cursor, hi)
            j += 1
        if cursor < b:
            rest.append((cursor, b))
    return rest


def idle_by_cause(summary, spans: Sequence[dict]) -> Dict[str, float]:
    """Idle nanoseconds of the first device (``summary.gaps``, as
    ``idle_share`` counts them) by the span the host was in; they sum to
    the device's idle time.  Checks the clock first (:func:`check_clock`)."""
    check_clock(summary, spans)
    spans = complete(spans)
    work = [sp for sp in spans if not sp["name"].endswith("_wait")]
    waits = [sp for sp in spans if sp["name"].endswith("_wait")]
    out: Dict[str, float] = {}
    rest = _credit(list(summary.gaps), segments(work), out)
    rest = _credit(rest, segments(waits), out)
    left = sum(b - a for a, b in rest)
    if left:
        out[NO_REQUEST] = out.get(NO_REQUEST, 0.0) + left
    return out


def clock_share(summary, spans: Sequence[dict]) -> Optional[float]:
    """Share of the step programs' device time (``XLA Modules`` events in
    the window) that lies inside some ``steps`` span; None where there is
    no such program time or no such span."""
    steps = trace_reduce.union(interval(sp) for sp in complete(spans)
                               if sp["name"] == "steps")
    programs = trace_reduce.union(
        iv for dev in summary.devices
        for iv in trace_reduce.clip(
            [e for e in dev.modules()
             if STEP_PROGRAMS.search(trace_reduce.op_group(e[0]))],
            summary.t0, summary.t1))
    if not steps or not programs:
        return None
    starts = [s for s, _ in steps]
    inside = 0
    for a, b in programs:
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(steps) and steps[j][0] < b:
            inside += max(0, min(b, steps[j][1]) - max(a, steps[j][0]))
            j += 1
    return inside / sum(b - a for a, b in programs)


def check_clock(summary, spans: Sequence[dict]) -> Optional[float]:
    """:func:`clock_share`, raising :class:`ClockMismatch` below
    :data:`CLOCK_MIN_SHARE`."""
    share = clock_share(summary, spans)
    if share is not None and share < CLOCK_MIN_SHARE:
        raise ClockMismatch(
            f"{100 * share:.2f}% of the step programs' device time lies "
            f"inside steps spans (at least {100 * CLOCK_MIN_SHARE}% "
            f"needed): host spans and device events are not on one clock")
    return share


def idle(ctx) -> Optional[Dict[str, float]]:
    """:func:`idle_by_cause` of a run, computed once per run; None without
    a device trace or without the program's item spans."""
    if ctx.summary is None or not traced(ctx.spans):
        return None
    cached = getattr(ctx, "_idle_by_cause", None)
    if cached is None:
        cached = ctx._idle_by_cause = idle_by_cause(ctx.summary, ctx.spans)
    return cached


def idle_pct(ctx, name: str) -> Optional[float]:
    """Idle time credited to ``name``, % of the window."""
    by_cause = idle(ctx)
    if by_cause is None:
        return None
    return 100.0 * by_cause.get(name, 0.0) / ctx.summary.window_ns


def lane_turns(ctx) -> Optional[List[Tuple[float, float]]]:
    """Per answered request that entered a lane: (seconds from its entry
    — its first ``execute`` start or its ``join`` mark — to its
    ``deliver`` start, seconds of its own ``steps`` + ``host_compute``)."""
    if not traced(ctx.spans):
        return None
    answered = {r.handle.trace_id for r in ctx.answered()
                if r.handle is not None}
    entry: Dict[str, float] = {}
    deliver: Dict[str, float] = {}
    for sp in ctx.spans:
        tid, name = sp.get("trace_id"), sp.get("name")
        if tid not in answered or sp.get("t0") is None:
            continue
        t0 = float(sp["t0"])
        if name in ("execute", "join"):
            entry[tid] = min(t0, entry.get(tid, t0))
        elif name == "deliver":
            deliver[tid] = min(t0, deliver.get(tid, t0))
    own = per_request(ctx.spans, WORK)
    turns = [(deliver[t] - entry[t], own.get(t, 0.0))
             for t in entry if t in deliver]
    return turns or None
