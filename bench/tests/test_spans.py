"""bench/spans.py on a small recorded excerpt: self time, idle time split
equally among threads, ``no request in flight``, the clock check, and the
new readers' silence on a program without item spans."""

import types

import pytest

import run
import spans as sp_mod
import trace_reduce as tr

S = 1e-9   # seconds per ns: span times below are written in ns


def span(sid, name, t0_ns, dur_ns, tid=1, parent=None, trace="a", **attrs):
    return {"trace_id": trace, "span_id": sid, "parent": parent,
            "name": name, "t0": t0_ns * S, "dur_s": dur_ns * S, "pid": 7,
            "tid": tid, "attrs": attrs, "phase": "complete"}


def summary(ops, modules=(), t0=0, t1=1000):
    dev = tr.DeviceTrace("/device:TPU:0", {"XLA Ops": list(ops),
                                           "XLA Modules": list(modules)})
    return tr.summarize([dev], t0, t1)


# the device runs [100, 200) and [600, 700) in a window of [0, 1000):
# idle [0, 100), [200, 600), [700, 1000)
OPS = [("fusion.1", 100, 100), ("fusion.2", 600, 100)]
MODULES = [("jit__expand_step(3)", 100, 100),
           ("jit__expand_step(3)", 600, 100)]


def excerpt():
    return [
        span("e", "execute", 50, 850),
        span("s", "steps", 80, 700, parent="e", syncs=9, steps=4),
        span("c", "checkpoint", 300, 100, parent="s"),
        # another thread's work while the lane sits in [400, 500) of steps
        span("h", "host_compute", 400, 100, tid=2, trace="b"),
        # a wait emitted on the lane thread, covering the idle tail
        span("w", "queue_wait", 900, 80, tid=3, trace="c"),
        span("m", "deliver", 990, 0),                       # a mark
    ]


def test_self_time_subtracts_children():
    own = sp_mod.self_ns(excerpt())
    assert own["s"] == 700 - 100
    assert own["e"] == 850 - 700
    assert own["c"] == 100
    assert "m" not in own


def test_idle_is_credited_to_the_innermost_span_per_thread():
    by = sp_mod.idle_by_cause(summary(OPS, MODULES), excerpt())
    # [0, 50) nothing; [50, 80) execute; [80, 100) steps
    # [200, 300) steps; [300, 400) checkpoint; [400, 500) split steps /
    # host_compute; [500, 600) steps; [700, 780) steps; [780, 900) execute;
    # [900, 980) the wait alone; [980, 1000) nothing
    assert by[sp_mod.NO_REQUEST] == pytest.approx(50 + 20)
    assert by["execute"] == pytest.approx(30 + 120)
    assert by["steps"] == pytest.approx(20 + 100 + 50 + 100 + 80)
    assert by["checkpoint"] == pytest.approx(100)
    assert by["host_compute"] == pytest.approx(50)
    assert by["queue_wait"] == pytest.approx(80)
    idle = sum(e - s for s, e in summary(OPS, MODULES).gaps)
    assert sum(by.values()) == pytest.approx(idle)


def test_equal_split_among_threads_not_names():
    spans = [span("x", "steps", 0, 1000, tid=1),
             span("y", "steps", 0, 1000, tid=2, trace="b"),
             span("z", "checkpoint", 0, 1000, tid=3, trace="c")]
    by = sp_mod.idle_by_cause(summary([("op", 1000, 0)]), spans)
    assert by["steps"] == pytest.approx(2000 / 3)
    assert by["checkpoint"] == pytest.approx(1000 / 3)


def test_a_wait_yields_to_any_working_span():
    spans = [span("w", "lane_wait", 0, 1000, tid=1),
             span("p", "plan", 200, 100, tid=1, trace="b")]
    by = sp_mod.idle_by_cause(summary([]), spans)
    assert by == pytest.approx({"lane_wait": 900, "plan": 100})


def test_clock_check_raises_when_programs_fall_outside_steps():
    late = [("jit__expand_step(3)", 100, 100),
            ("jit__expand_step(3)", 800, 150)]   # after steps ended at 780
    s = summary(OPS, late)
    assert sp_mod.clock_share(s, excerpt()) == pytest.approx(100 / 250)
    with pytest.raises(sp_mod.ClockMismatch):
        sp_mod.idle_by_cause(s, excerpt())
    assert sp_mod.clock_share(summary(OPS, MODULES), excerpt()) == 1.0


def _quanta(shift_ns, stray=()):
    """20 ``steps`` spans of 400 us, 2 ms apart from 1 ms on, each running
    8 step programs of 10 us, 45 us apart; the device events moved by
    ``shift_ns``; ``stray`` programs besides."""
    us = 1000
    spans_, mods = [], []
    for i in range(20):
        t = (1000 + i * 2000) * us
        spans_.append(span(f"s{i}", "steps", t, 400 * us, trace=f"r{i}"))
        mods += [("jit_masked_kmeans_step_jit(1)", t + (5 + 45 * k) * us
                  + shift_ns, 10 * us) for k in range(8)]
    mods += [("jit_masked_kmeans_step_jit(1)", t, 10 * us) for t in stray]
    return summary([], mods, 0, 42_000 * us), spans_


def test_clock_check_judges_the_trace_as_it_reads():
    # no shift is searched for: device events read early enough to put
    # the first program of each span outside it cost their share
    assert sp_mod.check_clock(*_quanta(0)) == 1.0
    s, spans_ = _quanta(-30_000)
    assert sp_mod.clock_share(s, spans_) == pytest.approx(7 / 8)
    with pytest.raises(sp_mod.ClockMismatch):
        sp_mod.check_clock(s, spans_)
    s, spans_ = _quanta(-8_000)   # 3 of 80 us outside: 96.25%
    assert sp_mod.check_clock(s, spans_) == pytest.approx(77 / 80)


def test_clock_check_raises_for_step_programs_outside_any_span():
    # 40 programs of 10 us, 1 ms after spans end: a path that runs steps
    # without a span
    stray = [2_400_000 + i * 2_000_000 + k * 20_000
             for i in range(20) for k in range(2)]
    s, spans_ = _quanta(0, stray)
    assert sp_mod.clock_share(s, spans_) == pytest.approx(160 / 200)
    with pytest.raises(sp_mod.ClockMismatch):
        sp_mod.check_clock(s, spans_)


def test_no_clock_check_without_steps_or_step_programs():
    no_steps = [x for x in excerpt() if x["name"] != "steps"]
    assert sp_mod.clock_share(summary(OPS, MODULES), no_steps) is None
    assert sp_mod.clock_share(summary(OPS, []), excerpt()) is None


def _ctx(spans_, summary_=None, answered=()):
    records = [types.SimpleNamespace(
        result={"executor": "jax-ref"}, req=types.SimpleNamespace(algo="a"),
        handle=types.SimpleNamespace(trace_id=t)) for t in answered]
    window = types.SimpleNamespace(records=records, lateness=[])
    return run.Context(window, 0.0, spans_, summary_, {}, 0)


NEW = ("item_ms.open", "item_ms.closed", "turn_wait_ms.open",
       "turn_wait_ms.closed", "syncs_per_step.open", "syncs_per_step.closed",
       "idle_in_steps.closed", "idle_in_checkpoint.closed")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_for_a_program_without_item_spans(name):
    old = [x for x in excerpt() if x["name"] not in sp_mod.WORK]
    ctx = _ctx(old, summary(OPS, MODULES), answered=("a",))
    assert run.load_reader(name)(ctx) is None


def test_new_readers_read_the_excerpt():
    spans = excerpt() + [span("d", "deliver", 960, 10)]
    ctx = _ctx(spans, summary(OPS, MODULES), answered=("a",))
    read = run.load_reader
    assert read("item_ms.closed")(ctx) == pytest.approx(700e-6)
    # entry at execute (50), deliver at 960: 910 ns in the lane, 700 own
    assert read("turn_wait_ms.closed")(ctx) == pytest.approx(210e-6)
    assert read("syncs_per_step.closed")(ctx) == pytest.approx(9 / 4)
    assert read("idle_in_steps.closed")(ctx) == pytest.approx(
        100 * 350 / 1000)
    assert read("idle_in_checkpoint.closed")(ctx) == pytest.approx(10.0)
