"""Roofline counts use the logical shapes, and the peak table refuses an
unknown device."""

import types

import pytest

import peaks
import roofline
import run
import trace_reduce

V5E = peaks.peaks_for("TPU v5 lite")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("TPU v9 imaginary")


def test_kmeans_step_counts():
    w = roofline.kmeans_step(n=1000, k=3, d=2)
    assert w.flops == 2 * 1000 * 3 * 2 + 1000 * 2
    assert w.bytes == 4 * 1000 * 2 + 4 * 3 * 2 + 4 * 1000 + 4 * 3 * 2
    t, bound = roofline.least_seconds(w, V5E)
    assert bound == "memory" and t == pytest.approx(w.bytes / 819e9)


def test_neighbor_counts_are_compute_bound():
    deg = roofline.neighbor_degree(16384, 4)
    exp = roofline.neighbor_expand(16384, 4)
    assert deg.flops == 2 * 16384 ** 2 * 4
    assert exp.flops == 0 and exp.bytes == 2 * 16384
    req = roofline.neighbor_request(16384, 4, expansions=25)
    assert req.flops == deg.flops
    assert roofline.least_seconds(req, V5E)[1] == "compute"


def _frontier_only_work(n, d, frontiers):
    """What an implementation does that runs the degree pass once and
    then, at each expansion, only the frontier's rows against all points."""
    work = roofline.Work(2.0 * n * n * d, 4.0 * n * d + 4.0 * n)
    for f in frontiers:
        work = work + roofline.Work(2.0 * f * n * d, 4.0 * f * d + 2.0 * n)
    return work


def _stored_adjacency_work(n, d, frontiers):
    """What an implementation does that keeps the degree pass's adjacency
    as a bit matrix and reads the frontier's rows of it."""
    work = roofline.Work(2.0 * n * n * d, 4.0 * n * d + n * n / 8.0)
    for f in frontiers:
        work = work + roofline.Work(0.0, f * n / 8.0 + 2.0 * n)
    return work


@pytest.mark.parametrize("implementation", [_frontier_only_work,
                                            _stored_adjacency_work])
@pytest.mark.parametrize("n,d", [(8192, 1), (16384, 4), (65536, 4)])
def test_no_leaner_expansion_reads_over_100_percent(implementation, n, d):
    """Each core point joins one frontier: the frontiers of a request sum
    to at most n.  A leaner implementation, run at the chip's peak, still
    needs at least the counted work, so its share stays at or under
    100%."""
    frontiers = [1, 7, 60, 400, n - 468]
    counted = roofline.neighbor_request(n, d, expansions=len(frontiers) + 1)
    done = implementation(n, d, frontiers)
    t_done = max(done.flops / V5E["bf16_flops"],
                 done.bytes / V5E["hbm_bandwidth"])
    least, _ = roofline.least_seconds(counted, V5E)
    assert least <= t_done


def test_neighbor_roofline_does_not_grow_with_expansions():
    """Recomputing all distances at every expansion is not counted as
    work the algorithm needs: more expansions at the same kernel time read
    as (almost) the same share, not a proportionally larger one."""
    import loadgen

    shape = loadgen.Shape(features=4, clusters=8, points_per_cluster=2048)
    reads = []
    for expansions in (5, 50):
        rec = _record(shape, "dbscan", {"executor": "pallas-kernel",
                                        "expansions": expansions})
        dev = trace_reduce.DeviceTrace("/device:TPU:0", {
            "XLA Modules": [("jit__degree_step(3)", 0, 1_000_000)],
            "XLA Ops": [("%degree_kernel.1 = f32[] custom-call()", 0,
                         1_000_000)]})
        summary = trace_reduce.summarize([dev], 0, 2_000_000)
        ctx = run.Context(types.SimpleNamespace(records=[rec]), 0.0, [],
                          summary, V5E, 0)
        reads.append(run.load_reader("neighbor_roofline")(ctx))
    least, _ = roofline.least_seconds(roofline.neighbor_degree(16384, 4),
                                      V5E)
    assert reads[0] == pytest.approx(100 * least / 1e-3)
    assert reads[1] == pytest.approx(reads[0])


def _ctx(records, module_ns):
    dev = trace_reduce.DeviceTrace("/device:TPU:0", {
        "XLA Modules": [("jit_fused_masked_kmeans_step_jit(1)", 0,
                         module_ns)],
        "XLA Ops": [("fusion", 0, module_ns)]})
    summary = trace_reduce.summarize([dev], 0, module_ns * 2)
    window = types.SimpleNamespace(records=records)
    return run.Context(window, 0.0, [], summary, V5E, 0)


def _record(shape, algo, result):
    req = types.SimpleNamespace(shape=shape, algo=algo)
    return types.SimpleNamespace(req=req, result=result)


def test_kmeans_roofline_reads_logical_not_padded_shape():
    import loadgen

    # 6 clusters of 128 points: n = 768 runs padded to 1024 (and d to 128)
    shape = loadgen.Shape(features=2, clusters=6, points_per_cluster=128)
    rec = _record(shape, "kmeans", {"executor": "pallas-kernel",
                                    "iterations": 10})
    ctx = _ctx([rec], module_ns=10_000)
    got = run.load_reader("kmeans_step_roofline")(ctx)
    least, _ = roofline.least_seconds(roofline.kmeans_step(768, 6, 2), V5E)
    assert got == pytest.approx(100 * 10 * least / 10e-6)
    padded, _ = roofline.least_seconds(roofline.kmeans_step(1024, 6, 128),
                                       V5E)
    assert got < 100 * 10 * padded / 10e-6


def test_roofline_is_silent_without_device_work():
    import loadgen

    shape = loadgen.Shape(2, 2, 128)
    rec = _record(shape, "kmeans", {"executor": "numpy-mt",
                                    "iterations": 10})
    ctx = _ctx([rec], module_ns=10_000)
    assert run.load_reader("kmeans_step_roofline")(ctx) is None
    assert run.load_reader("device_ms_per_lloyd_iter")(ctx) is None
