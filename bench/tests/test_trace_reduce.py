"""The reduction from device events to busy time, idle gaps, per-name and
collective time gives known numbers."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _two_devices():
    d0 = tr.DeviceTrace("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 100, 50), ("fusion.2", 120, 60),
                    ("collective-permute-done.3", 300, 100),
                    ("custom-call.4", 900, 100)],
        "XLA Modules": [("jit_step(7)", 100, 300), ("jit_step(8)", 900, 100)],
    })
    d1 = tr.DeviceTrace("/device:TPU:1", {
        "XLA Ops": [("fusion.1", 0, 200), ("all-reduce.9", 500, 100)],
        "XLA Modules": [("jit_step(7)", 0, 600)],
    })
    return [d0, d1]


def test_union_and_gaps():
    d0, _ = _two_devices()
    # window [0, 1000): ops cover 100-180, 300-400, 900-1000
    assert tr.busy_ns(d0.ops(), 0, 1000) == 80 + 100 + 100
    assert tr.idle_gaps(d0.ops(), 0, 1000) == [(0, 100), (180, 300),
                                               (400, 900)]
    # clipping at the window's edges
    assert tr.busy_ns(d0.ops(), 150, 950) == 30 + 100 + 50


def test_summary_numbers():
    s = tr.summarize(_two_devices(), 0, 1000)
    assert s.busy_ns == [280, 300]
    assert s.busy_s == pytest.approx(290e-9)
    assert s.window_s == pytest.approx(1e-6)
    assert s.collective_ns == [100, 100]
    assert s.op_ns["fusion"] == 50 + 60 + 200
    assert s.op_ns["collective-permute-done"] == 100
    import re
    assert s.module_ns(re.compile("jit_step")) == 300 + 100 + 600


def test_gap_attribution_prefers_work_over_waiting():
    gaps = [(0, 100), (180, 300)]
    spans = [
        {"name": "queue_wait", "t0": 0.0, "dur_s": 300e-9},
        {"name": "wal_append", "t0": 190e-9, "dur_s": 50e-9},
    ]
    got = tr.attribute_gaps(gaps, spans)
    assert got == {"queue_wait": 100, "wal_append": 120}
    assert tr.attribute_gaps([(0, 10)], []) == {"no request in flight": 10}


def test_breakdown_shape():
    s = tr.summarize(_two_devices(), 0, 1000)
    b = tr.breakdown(s, [], top=2)
    assert len(b["device_ops"]) == 2
    assert b["device_ops"][0] == ["fusion", pytest.approx(310e-9)]
    assert b["idle_gaps"] == [["no request in flight", pytest.approx(720e-9)]]


def test_recorded_tpu_trace():
    """30 ms of a DBSCAN cell's trace on a TPU v5e (data/)."""
    import re

    import numpy as np

    with open(os.path.join(HERE, "data", "tpu_dbscan_trace.json")) as fh:
        devs, t0, t1 = tr.from_excerpt(json.load(fh))
    s = tr.summarize(devs, t0, t1)
    # the same busy time by marking every busy nanosecond
    marked = np.zeros(t1 - t0, bool)
    for _, start, dur in devs[0].ops():
        marked[max(start, t0) - t0:min(start + dur, t1) - t0] = True
    assert s.busy_ns == [int(marked.sum())] == [5459825]
    assert len(s.gaps) == 61
    assert sum(e - b for b, e in s.gaps) == 30_000_000 - 5459825
    assert s.op_ns["expand_kernel"] == 5204754
    assert s.collective_ns == [0]
    assert s.module_ns(re.compile("_expand_step")) == 5441358
    assert s.module_ns(re.compile("_degree_step")) == 0


def test_a_trace_without_the_ops_line_is_an_error():
    dev = tr.DeviceTrace("/device:TPU:0", {
        "XLA Modules": [("jit_step(7)", 0, 600)]})
    with pytest.raises(ValueError, match="XLA Ops"):
        dev.ops()
    with pytest.raises(ValueError):
        tr.summarize([dev], 0, 1000)
