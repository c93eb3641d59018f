"""``steps_per_program.closed`` is read by its base from the DBSCAN items'
``steps`` spans, and is absent where no span counts programs."""

import pytest

import run


def _ctx(spans):
    return run.Context(None, 0.0, spans, None, {}, 0)


def _steps(algo, steps, programs=None, dur_s=0.1):
    attrs = {"algo": algo, "steps": steps, "syncs": 2 * steps}
    if programs is not None:
        attrs["programs"] = programs
    return {"name": "steps", "t0": 1.0, "dur_s": dur_s, "attrs": attrs}


def test_steps_per_program_sums_the_dbscan_items():
    read = run.load_reader("steps_per_program.closed")
    spans = [_steps("dbscan", 18, 4), _steps("dbscan", 10, 3),
             _steps("kmeans", 40, 40),
             {"name": "checkpoint", "t0": 1.0, "dur_s": 0.01,
              "attrs": {"programs": 9}},
             _steps("dbscan", 50, 1, dur_s=None)]     # never finished
    assert read(_ctx(spans)) == pytest.approx(28 / 7)


def test_steps_per_program_is_absent_without_the_counter():
    read = run.load_reader("steps_per_program.closed")
    assert read(_ctx([_steps("dbscan", 18), _steps("kmeans", 5, 5)])) is None
    assert read(_ctx([])) is None
