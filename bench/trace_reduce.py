"""From a profiler trace to device busy and idle time, per-kernel time,
collective time and the ``breakdown``.

The JAX profiler writes an ``.xplane.pb``.  :func:`load` reads it with
``jax.profiler.ProfileData`` into plain event lists on the host's wall
clock (nanoseconds since the epoch), one list per line of each device
plane, so that the reduction below runs on recorded data as well
(``bench/tests`` checks it on a small recorded trace).

On a TPU each device plane (``/device:TPU:<i>``) has a line of XLA
programs (``XLA Modules``: one event per program execution, named after
the jitted function) and a line of XLA operations (``XLA Ops``: one event
per HLO operation, fusion or Pallas kernel).  Busy time is the union of the
operation intervals inside the traced window; idle time is the rest.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|psum", re.IGNORECASE)

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


@dataclass
class DeviceTrace:
    """The events of one device, on the host's wall clock (ns)."""

    name: str
    lines: Dict[str, List[Event]] = field(default_factory=dict)

    def ops(self) -> List[Event]:
        """The ``XLA Ops`` line.  A trace without it is of a format that
        no reduction here was checked on, so it is an error, not a reason
        to count another line's events as busy time."""
        if OPS_LINE not in self.lines:
            raise ValueError(f"{self.name} has no {OPS_LINE!r} line; lines: "
                             f"{sorted(self.lines)}")
        return self.lines[OPS_LINE]

    def modules(self) -> List[Event]:
        return self.lines.get(MODULES_LINE, [])


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> List[DeviceTrace]:
    """Device planes of one ``.xplane.pb``, sorted by device index."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    base = 0
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                base = int(value)
    devices = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = DeviceTrace(plane.name)
        for line in plane.lines:
            dev.lines[line.name] = [
                (ev.name, base + int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]
        devices.append((int(m.group(2)), dev))
    return [d for _, d in sorted(devices, key=lambda t: t[0])]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if s + d > t0 and s < t1]


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    return sum(e - s for s, e in union(clip(events, t0, t1)))


def idle_gaps(events: Sequence[Event], t0: int,
              t1: int) -> List[Tuple[int, int]]:
    """The [start, end) stretches of the window in which no event ran."""
    gaps = []
    cursor = t0
    for s, e in union(clip(events, t0, t1)):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    return gaps


def time_by_name(events: Sequence[Event], t0: int, t1: int,
                 normalize=None) -> Dict[str, int]:
    """Summed in-window duration per event name (``normalize`` maps a name
    to its group, e.g. strips a trailing program id)."""
    out: Dict[str, int] = {}
    for name, s, d in events:
        e = min(s + d, t1)
        s = max(s, t0)
        if e <= s:
            continue
        key = normalize(name) if normalize else name
        out[key] = out.get(key, 0) + (e - s)
    return out


def matching_ns(events: Sequence[Event], pattern: "re.Pattern", t0: int,
                t1: int) -> int:
    """In-window time covered by the events whose name matches (the
    operation's own name, not its operands)."""
    return busy_ns([e for e in events if pattern.search(op_group(e[0]))],
                   t0, t1)


_PROGRAM_ID = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"[.:]\d+$")


def op_group(name: str) -> str:
    """The operation's name without its instance number: the trace names an
    operation by its HLO text, ``%fusion.12 = f32[...] fusion(...)`` ->
    ``fusion``; ``jit_step(42)`` -> ``jit_step``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return _OP_SUFFIX.sub("", _PROGRAM_ID.sub("", name))


def attribute_gaps(gaps: Sequence[Tuple[int, int]],
                   spans: Sequence[dict]) -> Dict[str, int]:
    """Idle nanoseconds by what the host was doing, read from the service's
    spans (wall-clock ``t0`` seconds, ``dur_s``).  A gap is credited to the
    working span (not a ``*_wait``) that overlaps it most, else to the wait
    span that overlaps it most, else to ``no request in flight``."""
    ivs = []
    for sp in spans:
        dur = sp.get("dur_s")
        if not dur or sp.get("phase") == "start":
            continue
        s = int(float(sp["t0"]) * 1e9)
        ivs.append((s, s + int(float(dur) * 1e9), str(sp["name"])))
    ivs.sort()
    out: Dict[str, int] = {}
    starts = [iv[0] for iv in ivs]
    for gs, ge in gaps:
        best: Dict[bool, Tuple[int, str]] = {}
        hi = bisect.bisect_left(starts, ge)
        for s, e, name in ivs[:hi]:
            ov = min(e, ge) - max(s, gs)
            if ov <= 0:
                continue
            waiting = name.endswith("_wait")
            if ov > best.get(waiting, (0, ""))[0]:
                best[waiting] = (ov, name)
        label = (best.get(False) or best.get(True)
                 or (0, "no request in flight"))[1]
        out[label] = out.get(label, 0) + (ge - gs)
    return out


@dataclass
class Summary:
    """Reduction of one traced window over the devices a cell uses."""

    window_ns: int
    busy_ns: List[int]
    collective_ns: List[int]
    op_ns: Dict[str, int]
    gaps: List[Tuple[int, int]]
    devices: List[DeviceTrace]
    t0: int
    t1: int

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def module_ns(self, pattern: "re.Pattern") -> int:
        """Summed device time of matching programs, over all devices."""
        return sum(matching_ns(d.modules(), pattern, self.t0, self.t1)
                   for d in self.devices)


def summarize(devices: List[DeviceTrace], t0: int, t1: int) -> Summary:
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = [busy_ns(d.ops(), t0, t1) for d in devices]
    coll = [matching_ns(d.ops(), COLLECTIVE, t0, t1) for d in devices]
    op_ns: Dict[str, int] = {}
    for d in devices:
        for k, v in time_by_name(d.ops(), t0, t1, op_group).items():
            op_ns[k] = op_ns.get(k, 0) + v
    return Summary(window_ns=t1 - t0, busy_ns=busy, collective_ns=coll,
                   op_ns=op_ns, gaps=idle_gaps(devices[0].ops(), t0, t1),
                   devices=devices, t0=t0, t1=t1)


def breakdown(summary: Summary, spans: Sequence[dict],
              top: int = 10) -> dict:
    """The ``breakdown`` of a result line: device operations that took most
    time (seconds summed over the cell's devices), and idle seconds of the
    first device by what the host was doing."""
    ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(attribute_gaps(summary.gaps, spans).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def from_excerpt(data: dict) -> Tuple[List[DeviceTrace], int, int]:
    """Devices and window of a recorded trace kept as JSON:
    ``{"t0", "t1", "devices": [{"name", "lines": {line: [[name, start_ns,
    duration_ns], ...]}}]}``."""
    devs = [DeviceTrace(d["name"], {ln: [tuple(e) for e in evs]
                                     for ln, evs in d["lines"].items()})
            for d in data["devices"]]
    return devs, int(data["t0"]), int(data["t1"])
