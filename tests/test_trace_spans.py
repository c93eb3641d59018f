"""Spans inside execute: parent links, native thread ids, the per-item
``steps`` span with its step and sync counters, ``host_compute`` on the
host lane, ``compile`` spans, the profiler mirror, and the measured
``device_s`` of a batch."""

import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.service import ClusteringService, MiningClient, RequestTracer
from repro.service import read_spans
from repro.service.dispatch import (
    EXECUTOR_JAX_REF,
    EXECUTOR_NUMPY_MT,
    ItemProbe,
    ItemView,
    default_registry,
    far_diagonal_pad,
)
from repro.service.telemetry import render_prometheus
from repro.service.trace import chrome_trace

DOCS = os.path.join(os.path.dirname(__file__), "..", "docs", "OPERATIONS.md")


def blobs(seed, clusters=3, per=40, d=2, spread=0.5):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-20.0, 20.0, size=(clusters, d))
    x = np.concatenate([c + rng.normal(0.0, spread, size=(per, d))
                        for c in centers]).astype(np.float32)
    return x[rng.permutation(len(x))]


# -- parents and thread ids ----------------------------------------------------


def test_nested_begins_link_to_the_innermost_open_span_of_their_trace():
    tr = RequestTracer()
    with tr.begin("t1", "outer") as outer:
        with tr.begin("t2", "other") as other:
            with tr.begin("t1", "inner") as inner:
                tr.emit("t1", "retro", 0.0, 0.0, parent=inner.span_id)
            tr.emit("t1", "orphan", 0.0, 0.0)
    spans = {s.name: s for s in tr.spans()}
    assert spans["outer"].parent is None
    assert spans["other"].parent is None           # another trace
    assert spans["inner"].parent == outer.span_id  # skips t2's span
    assert spans["retro"].parent == inner.span_id  # explicit
    assert spans["orphan"].parent is None          # retroactive: none
    assert other.span_id != inner.span_id
    # the stack unwinds: a new begin after the block has no parent
    with tr.begin("t1", "later"):
        pass
    assert tr.spans()[-1].parent is None


def test_parents_do_not_cross_threads_unless_given():
    tr = RequestTracer()
    seen = {}
    with tr.begin("t1", "lane") as lane:
        def worker():
            with tr.begin("t1", "pool_default"):
                pass
            with tr.begin("t1", "pool_given", parent=lane.span_id):
                with tr.begin("t1", "pool_child") as child:
                    seen["child"] = child
            seen["tid"] = threading.get_native_id()

        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
    spans = {s.name: s for s in tr.spans()}
    assert spans["pool_default"].parent is None
    assert spans["pool_given"].parent == lane.span_id
    assert spans["pool_child"].parent == spans["pool_given"].span_id
    assert spans["pool_given"].tid == seen["tid"]


def test_tid_is_the_native_thread_id():
    tr = RequestTracer()
    tr.mark("t1", "here")
    with tr.begin("t1", "begun"):
        pass
    assert {s.tid for s in tr.spans()} == {threading.get_native_id()}


def test_parent_rides_in_exports(tmp_path):
    svc = ClusteringService(str(tmp_path), max_batch=1, max_wait_s=0.005)
    client = MiningClient(service=svc)
    with svc:
        h = client.submit("t", "kmeans", blobs(1), params={"k": 3, "seed": 1},
                          executor=EXECUTOR_JAX_REF)
        h.result(300)
    disk = read_spans(os.path.join(str(tmp_path), "events"), h.trace_id)
    by_name = {s["name"]: s for s in disk}
    assert by_name["wal_append"]["parent"] == by_name["submit"]["span_id"]
    assert by_name["steps"]["parent"] == by_name["execute"]["span_id"]
    doc = chrome_trace(disk)
    args = [e["args"] for e in doc["traceEvents"] if e["name"] == "steps"]
    assert args and args[0]["parent"] == by_name["execute"]["span_id"]


# -- steps spans and their counters -------------------------------------------


def _run_item(lane, algo, x, params, *, state_interval, continuous=False):
    """One item through a paradigm the way the batch executor drives it:
    every checkpoint hook reads its state back."""
    paradigm = default_registry().get(lane)
    n, d = x.shape
    n_pad = 1 << (n - 1).bit_length()
    x_pad = np.zeros((n_pad, d), np.float32)
    x_pad[:n] = x
    if algo == "dbscan":
        far_diagonal_pad(x_pad, n, params["eps"], float(x.max()))
    plan = paradigm.plan(algo, params, batch_size=1, n_max=n_pad, features=d)
    item = ItemView(index=0, x_pad=x_pad, length=n,
                    seed=int(params.get("seed", 0)))
    tracer = RequestTracer()
    probe = ItemProbe(tracer, ["t0"])
    answer = {}
    reads = []

    def on_done(_i, labels, scalars):
        answer.update(scalars, labels=labels)

    def on_state(_i, read):
        reads.append(read())

    out = paradigm.execute(plan, [item], None, on_done, on_state,
                           state_interval=state_interval,
                           boundary_hook=(lambda: []) if continuous else None,
                           probe=probe)
    assert not out.suspended
    return answer, tracer.spans(), probe, reads


def test_dbscan_item_counts_its_steps_and_syncs():
    si = 4
    x = blobs(2, clusters=3, per=60)
    answer, spans, probe, reads = _run_item(
        EXECUTOR_JAX_REF, "dbscan", x, {"eps": 1.5, "min_pts": 4},
        state_interval=si)
    (steps,) = [s for s in spans if s.name == "steps"]
    e, c = answer["expansions"], answer["n_clusters"]
    assert e >= si and c >= 2
    assert steps.attrs["steps"] == e + 1          # the degree pass + each
    # the degree pass, then one program per stretch of si expansions, the
    # last one ending where the run does
    chunks = -(-e // si)
    assert steps.attrs["programs"] == chunks + 1
    # one read per program of (finished, cluster id, expansions); a
    # snapshot of three reads at each multiple of si short of the end;
    # pack_state's bound; labels and expansions
    snapshots = chunks - 1
    assert steps.attrs["syncs"] == chunks + 3 * snapshots + 3
    assert len(reads) == snapshots
    assert steps.attrs["lane"] == EXECUTOR_JAX_REF
    assert steps.attrs["algo"] == "dbscan" and steps.attrs["d"] == 2
    assert 0.0 < steps.attrs["sync_s"] <= steps.dur_s
    assert probe.sync_s == pytest.approx(steps.attrs["sync_s"])


@pytest.mark.parametrize("continuous", [False, True])
def test_kmeans_item_counts_its_steps_and_syncs(continuous):
    si = 2
    x = blobs(3, clusters=4, per=50, spread=3.0)
    answer, spans, probe, reads = _run_item(
        EXECUTOR_JAX_REF, "kmeans", x,
        {"k": 4, "seed": 5, "tol": 1e-6, "max_iters": 40},
        state_interval=si, continuous=continuous)
    steps = [s for s in spans if s.name == "steps"]
    it = answer["iterations"]
    assert it > 2 * si
    assert sum(s.attrs["steps"] for s in steps) == it
    assert all(s.attrs["programs"] == s.attrs["steps"] for s in steps)
    syncs = sum(s.attrs["syncs"] for s in steps)
    if continuous:
        # one span per quantum of si iterations: a shift read per
        # iteration, a state read-back after each unfinished quantum, and
        # the answer's three reads after the last
        quanta = -(-it // si)
        assert len(steps) == quanta
        assert syncs == it + (quanta - 1) + 3
        assert len(reads) == quanta - 1
    else:
        assert len(steps) == 1
        assert syncs == it + it // si + 3
        assert len(reads) == it // si
    assert probe.sync_s == pytest.approx(
        sum(s.attrs["sync_s"] for s in steps))


def test_numpy_item_is_host_compute():
    x = blobs(4)
    answer, spans, probe, _ = _run_item(
        EXECUTOR_NUMPY_MT, "kmeans", x, {"k": 3, "seed": 1},
        state_interval=8)
    (host,) = [s for s in spans if s.name == "host_compute"]
    assert host.attrs["iterations"] == answer["iterations"]
    assert host.attrs["n"] == len(x) and host.attrs["algo"] == "kmeans"
    assert not [s for s in spans if s.name == "steps"]
    assert probe.sync_s == 0.0


def test_checkpoint_is_a_child_of_steps_and_device_s_is_measured(tmp_path):
    svc = ClusteringService(str(tmp_path), max_batch=1, max_wait_s=0.005,
                            checkpoint_every=2)
    client = MiningClient(service=svc)
    with svc:
        h = client.submit("t", "dbscan", blobs(5, per=60),
                          params={"eps": 1.5, "min_pts": 4},
                          executor=EXECUTOR_JAX_REF)
        assert h.result(300)["expansions"] >= 2
    spans = svc.export_trace(h.trace_id)
    (steps,) = [s for s in spans if s["name"] == "steps"]
    inner = [s for s in spans if s["name"] == "checkpoint"
             and s["parent"] == steps["span_id"]]
    assert inner and all(s["t0"] >= steps["t0"] for s in inner)
    ex = svc.metrics_snapshot()["by_executor"][EXECUTOR_JAX_REF]
    assert ex["device_s"] == pytest.approx(steps["attrs"]["sync_s"])


# -- compiles ------------------------------------------------------------------


def test_a_new_shape_emits_a_compile_span_under_its_span(tmp_path):
    tr = RequestTracer()

    def triple_plus_seven(x):
        return x * 3.0 + 7.0

    with tr.begin("t1", "steps") as steps:
        jax.jit(triple_plus_seven)(jnp.ones((3, 17))).block_until_ready()
    compiles = [s for s in tr.spans() if s.name == "compile"]
    assert compiles and all(s.parent == steps.span_id for s in compiles)
    assert any("triple_plus_seven" in s.attrs["fun_name"] for s in compiles)
    assert tr.compiles == len(compiles)
    (outer,) = [s for s in tr.spans() if s.name == "steps"]
    assert all(outer.t0 <= s.t0 and s.dur_s <= outer.dur_s
               for s in compiles)
    # with no span open on the thread a compile is nobody's request's
    before = tr.compiles
    jax.jit(triple_plus_seven)(jnp.ones((5, 19))).block_until_ready()
    assert tr.compiles == before


def test_backend_compiles_are_exported(tmp_path):
    svc = ClusteringService(str(tmp_path), max_batch=1, max_wait_s=0.005)
    client = MiningClient(service=svc)
    with svc:
        client.submit("t", "kmeans", blobs(6, per=37), params={
            "k": 3, "seed": 2}, executor=EXECUTOR_JAX_REF).result(300)
    snap = svc.metrics_snapshot()
    assert snap["backend"]["compiles"] == svc.tracer.compiles
    text = render_prometheus(snap)
    assert re.search(r"^repro_backend_compiles_total \d+", text, re.M)


# -- the profiler mirror -------------------------------------------------------


def test_span_annotation_lies_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    tr = RequestTracer()
    x = jnp.ones((64, 64))
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with tr.begin("abc123", "steps"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    base = 0
    starts = []
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                base = int(value)
        for line in plane.lines:
            starts += [ev.start_ns for ev in line.events
                       if ev.name == "steps"]
    spans = [s for s in tr.spans() if s.name == "steps"]
    assert len(starts) == len(spans) == 3
    for span, start in zip(spans, sorted(starts)):
        assert abs(base + int(start) - span.t0 * 1e9) < 1e6


def test_annotation_mirror_follows_a_capture(tmp_path):
    from repro.service import trace

    annotation = trace._annotation_class()
    assert annotation is jax.profiler.TraceAnnotation
    assert annotation.is_enabled() is False
    tr = RequestTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert annotation.is_enabled() is True
        with tr.begin("abc123", "steps") as handle:
            assert handle._annotation is not None
    finally:
        jax.profiler.stop_trace()
    with tr.begin("abc123", "steps") as handle:
        assert handle._annotation is None


# -- the documented stage list -------------------------------------------------


def test_every_span_of_a_request_run_is_documented(tmp_path):
    with open(DOCS) as fh:
        doc = fh.read()
    telemetry = doc[doc.index("## Telemetry"):]
    telemetry = telemetry[: telemetry.index("### Scraping")]
    documented = set(re.findall(r"`([a-z_]+)`", telemetry))
    svc = ClusteringService(str(tmp_path), max_batch=2, max_wait_s=0.005)
    client = MiningClient(service=svc)
    with svc:
        hs = [client.submit("t", "kmeans", blobs(7, per=41),
                            params={"k": 3, "seed": 3},
                            executor=EXECUTOR_JAX_REF),
              client.submit("t", "dbscan", blobs(8, per=43),
                            params={"eps": 1.5, "min_pts": 4},
                            executor=EXECUTOR_JAX_REF),
              client.submit("t", "kmeans", blobs(9, per=39),
                            params={"k": 3, "seed": 4},
                            executor=EXECUTOR_NUMPY_MT)]
        for h in hs:
            h.result(300)
        # the same points again: answered from the result cache
        client.submit("t", "kmeans", blobs(7, per=41),
                      params={"k": 3, "seed": 3},
                      executor=EXECUTOR_JAX_REF).result(300)
    names = {s["name"] for s in svc.export_trace()}
    assert {"submit", "steps", "host_compute", "checkpoint"} <= names
    assert names <= documented, names - documented
