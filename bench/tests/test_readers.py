"""Metric readers are found by name, and a split metric by its base."""

import types

import pytest

import run


def _ctx(lateness):
    window = types.SimpleNamespace(records=[], lateness=lateness)
    return run.Context(window, 0.0, [], None, {}, 3)


@pytest.mark.parametrize("name", ["compiles_in_window.open",
                                  "compiles_in_window.closed"])
def test_split_metric_is_read_by_its_base(name):
    assert run.load_reader(name)(_ctx([])) == 3


def test_submit_stall_is_the_longest_lateness():
    read = run.load_reader("submit_stall_ms")
    assert read(_ctx([0.0004, 1.51, 0.09])) == pytest.approx(1510.0)
    assert read(_ctx([])) is None
