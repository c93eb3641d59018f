"""The control -- the reference in bfloat16, the precision below the
configuration's float32, put in the program's place -- fails the
configured limits, at a size a test run holds."""

import os

import pytest

import compare
import control
import loadgen
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Rec:
    def __init__(self, req, result):
        self.req = req
        self.result = result


@pytest.mark.parametrize("algo", ["kmeans", "dbscan"])
@pytest.mark.parametrize("seed", [1, 2**31 + 11, 2**33 + 3])
def test_control_is_not_correct(algo, seed):
    config = run.load_json(BENCH, "configs", "paper-grid.json")
    mix = {"loop": "closed", "algo": algo, "max_rate": 1,
           "points_per_cluster": [256, 512]}
    reqs = loadgen.plan(config, mix, seed, 1.0, count=6)
    loadgen.make_data(config, seed, reqs)
    rows = []
    for req in reqs:
        rec = _Rec(req, None)
        rec.result = control.control_answer(rec)
        rows.append(run.reference_numbers(rec))
    numbers = compare.worst(rows)
    numbers["unanswered"] = 0.0
    correct, checks = compare.judge(numbers, config["limits"])
    assert not correct, checks
