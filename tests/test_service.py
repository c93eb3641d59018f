"""Clustering-as-a-service tests: admission fairness, coalescing, dispatch,
caching, metrics, and the preemption/crash resume paths (batch jobs +
checkpoints), including a real SIGKILL subprocess restart."""

import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dbscan, kmeans
from repro.core.cancellation import CancellationToken, CancelReason
from repro.core.jobs import JobState, JobStore
from repro.data.synthetic import ClusterSpec, make_blobs
from repro.service import (
    AdmissionQueue,
    BacklogFull,
    BatchExecutor,
    BatchKey,
    ClusteringService,
    JobSuspended,
    MicroBatcher,
    MiningClient,
    MiningRequest,
    ResultCache,
    content_key,
    default_registry,
)
from repro.service.dispatch import (
    EXECUTOR_JAX_REF,
    EXECUTOR_NUMPY_MT,
    EXECUTOR_PALLAS,
)
from repro.service.executor import SERVICE_JOB_KIND
from repro.service.metrics import ServiceMetrics, percentile

DB_CFG = dbscan.DBSCANConfig.paper_defaults(2)
DB_PARAMS = {"eps": DB_CFG.eps, "min_pts": DB_CFG.min_pts}


def blob(seed, clusters=4, points=32, features=2):
    x, _, _ = make_blobs(jax.random.PRNGKey(seed),
                         ClusterSpec(features, clusters, points))
    return np.asarray(x, np.float32)


def req(tenant="t0", algo="dbscan", data=None, params=None, executor=None):
    if data is None:
        data = blob(0)
    if params is None:
        params = dict(DB_PARAMS) if algo == "dbscan" else {"k": 4}
    return MiningRequest(tenant=tenant, algo=algo, data=data,
                         params=dict(params), executor=executor)


# -- admission queue -----------------------------------------------------------


def test_queue_round_robin_fairness():
    q = AdmissionQueue()
    for i in range(6):
        q.submit(req(tenant="chatty"))
    q.submit(req(tenant="quiet"))
    drained = q.drain()
    # the quiet tenant's single request must ride in the first rotation
    assert [r.tenant for r in drained[:2]].count("quiet") == 1
    assert len(drained) == 7


def test_queue_backlog_bounds():
    q = AdmissionQueue(max_backlog=4, max_per_tenant=2)
    q.submit(req(tenant="a"))
    q.submit(req(tenant="a"))
    with pytest.raises(BacklogFull):   # per-tenant bound
        q.submit(req(tenant="a"))
    q.submit(req(tenant="b"))
    q.submit(req(tenant="c"))
    with pytest.raises(BacklogFull):   # global bound
        q.submit(req(tenant="d"))
    assert q.rejected == 2


def test_queue_validates_requests():
    q = AdmissionQueue()
    with pytest.raises(ValueError):
        q.submit(req(algo="apriori"))
    with pytest.raises(ValueError):
        q.submit(req(algo="kmeans", params={"k": 999}))   # k > n
    with pytest.raises(ValueError):
        q.submit(req(algo="dbscan", params={"eps": 1.0}))  # missing min_pts


# -- micro-batcher -------------------------------------------------------------


def test_batcher_coalesces_compatible_requests():
    q = AdmissionQueue()
    b = MicroBatcher(q, max_batch=4, max_wait_s=0.0)
    for tenant in ("a", "b", "c"):
        q.submit(req(tenant=tenant, data=blob(1, points=16)))
    q.submit(req(tenant="a", params={"eps": 0.5, "min_pts": 3}))  # other key
    batches = b.poll()
    sizes = sorted(batch.size for batch in batches)
    assert sizes == [1, 3]
    big = max(batches, key=lambda batch: batch.size)
    assert {r.tenant for r in big.requests} == {"a", "b", "c"}
    assert big.occupancy == 3 / 4
    assert big.n_max >= max(r.n_points for r in big.requests)
    assert big.n_max & (big.n_max - 1) == 0   # pow2 bucket


def test_batcher_full_batch_flushes_immediately():
    q = AdmissionQueue()
    b = MicroBatcher(q, max_batch=2, max_wait_s=60.0)
    for i in range(5):
        q.submit(req(tenant=f"t{i}", data=blob(2, points=8)))
    batches = b.poll()
    assert sorted(batch.size for batch in batches) == [2, 2]  # 1 staged
    assert b.pending() == 1


def test_batcher_deadline_flush():
    q = AdmissionQueue()
    b = MicroBatcher(q, max_batch=8, max_wait_s=0.05)
    q.submit(req())
    now = time.time()
    assert b.poll(now=now) == []              # not ripe yet
    assert b.pending() == 1
    batches = b.poll(now=now + 0.06)          # deadline passed
    assert len(batches) == 1 and batches[0].size == 1


def test_batcher_executor_override_splits_key():
    q = AdmissionQueue()
    b = MicroBatcher(q, max_batch=4, max_wait_s=0.0)
    q.submit(req(executor=EXECUTOR_JAX_REF))
    q.submit(req(executor=EXECUTOR_PALLAS))
    q.submit(req())
    assert len(b.poll()) == 3


# -- cache ---------------------------------------------------------------------


def test_cache_returns_isolated_copies():
    c = ResultCache()
    c.put("k", {"labels": np.array([1, 2, 3], np.int16)})
    first = c.get("k")
    first["labels"][0] = 99   # a tenant mutating its copy
    assert c.get("k")["labels"][0] == 1


def test_cache_content_addressing_and_lru():
    c = ResultCache(max_entries=2)
    x1, x2 = blob(1), blob(2)
    k1 = content_key("dbscan", DB_PARAMS, x1)
    assert content_key("dbscan", DB_PARAMS, x1) == k1       # deterministic
    assert content_key("dbscan", DB_PARAMS, x2) != k1       # data-sensitive
    assert content_key("kmeans", {"k": 4}, x1) != k1        # algo-sensitive
    # kmeans seed is per-item (not in the batch key) but must split cache keys
    assert (content_key("kmeans", {"k": 4, "seed": 1}, x1)
            != content_key("kmeans", {"k": 4, "seed": 2}, x1))
    c.put(k1, {"labels": np.ones(3)})
    assert c.get(k1)["labels"].sum() == 3
    c.put("k2", {"v": 1})
    c.put("k3", {"v": 2})   # evicts k1 (LRU)
    assert c.get(k1) is None
    assert c.stats()["entries"] == 2


# -- dispatch cost model -------------------------------------------------------


def test_dispatch_cost_model_and_override():
    reg = default_registry()
    # tiny work: host threads win (launch overhead dominates)
    assert reg.select("dbscan", n=64, d=2, batch_size=1,
                      params=DB_PARAMS) == EXECUTOR_NUMPY_MT
    # big work on CPU host: jitted XLA reference
    big = reg.select("dbscan", n=4096, d=4, batch_size=8, params=DB_PARAMS)
    assert big in (EXECUTOR_JAX_REF, EXECUTOR_PALLAS)
    # explicit override always wins and is validated
    assert reg.select("kmeans", n=8, d=2, batch_size=1, params={"k": 2},
                      explicit=EXECUTOR_PALLAS) == EXECUTOR_PALLAS
    with pytest.raises(KeyError):
        reg.select("kmeans", n=8, d=2, batch_size=1, params={"k": 2},
                   explicit="cuda")


# -- metrics -------------------------------------------------------------------


def test_metrics_percentiles_and_occupancy():
    assert percentile([], 50) == 0.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 99) == 4.0
    assert percentile([1.0, 2.0], 50) == 1.0   # nearest-rank, no round-half-up
    assert percentile([5.0], 99) == 5.0
    m = ServiceMetrics()
    m.record_batch(algo="dbscan", executor="jax-ref", size=3, capacity=4,
                   n_max=64, exec_s=2.0)
    m.record_request(tenant="a", algo="dbscan", executor="jax-ref",
                     latency_s=0.5)
    snap = m.snapshot()
    assert snap["mean_occupancy"] == 0.75
    assert snap["modeled_joules"] == pytest.approx(15.0)  # big class: 7.5 W x 2 s
    assert snap["by_executor"]["jax-ref"]["p50_latency_s"] == 0.5


# -- core support: overflow guard, resumable fits, masked step -----------------


def test_pack_state_overflow_raises():
    n = 4
    ok = jnp.full((n,), dbscan.MAX_CLUSTER_ID, jnp.int32)
    flags = jnp.zeros((n,), bool)
    word = dbscan.pack_state(ok, flags, flags, flags)
    assert int(dbscan.finish(word)[0]) == dbscan.MAX_CLUSTER_ID
    bad = jnp.full((n,), dbscan.MAX_CLUSTER_ID + 1, jnp.int32)
    with pytest.raises(ValueError, match="int16 state word"):
        dbscan.pack_state(bad, flags, flags, flags)


@pytest.mark.parametrize("state_interval", [1, 8])
def test_dbscan_resumable_continues_exactly(state_interval):
    x = jnp.asarray(blob(5, clusters=8, points=64))
    cfg = dbscan.DBSCANConfig(eps=DB_CFG.eps, min_pts=DB_CFG.min_pts,
                              use_kernel=False)
    full = dbscan.fit_cancellable(x, cfg)
    token = CancellationToken()
    seen = []

    def progress(cid, nexp):
        seen.append(nexp)
        if nexp >= 3:
            token.cancel()

    partial, state = dbscan.fit_resumable(x, cfg, token, on_progress=progress,
                                          state_interval=state_interval)
    assert partial.cancelled and state is not None
    # progress comes once per device program, at the end of each stretch
    # of state_interval expansions: cancellation is seen there
    assert state.nexp == (3 if state_interval == 1 else 8)
    assert seen == list(range(state_interval, state.nexp + 1, state_interval))
    # round-trip through the checkpointable tree form
    state = dbscan.DBSCANRunState.from_tree(state.as_tree())
    resumed, state2 = dbscan.fit_resumable(x, cfg, state=state)
    assert state2 is None and not resumed.cancelled
    assert (np.asarray(resumed.labels) == np.asarray(full.labels)).all()
    assert int(resumed.expansions) == int(full.expansions)


def test_masked_kmeans_step_ignores_padding():
    x = jnp.asarray(blob(6, clusters=3, points=32))
    cfg = kmeans.KMeansConfig(k=3, use_kernel=False)
    c0 = kmeans.init_centroids(jax.random.PRNGKey(1), x, cfg)
    pad = jnp.zeros((24, x.shape[1]), jnp.float32)
    x_pad = jnp.concatenate([x, pad])
    mask = jnp.arange(x_pad.shape[0]) < x.shape[0]
    a_ref, c_ref, shift_ref, inertia_ref = kmeans.kmeans_step(x, c0, cfg)
    a, c, shift, inertia = kmeans.masked_kmeans_step(x_pad, c0, mask, cfg)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), rtol=1e-5)
    np.testing.assert_allclose(float(inertia), float(inertia_ref), rtol=1e-5)
    assert (np.asarray(a)[: x.shape[0]] == np.asarray(a_ref)).all()


def test_jobstore_claim_specific(tmp_path):
    store = JobStore(str(tmp_path / "jobs.db"))
    j1 = store.enqueue("a", {})
    j2 = store.enqueue("b", {})
    job = store.claim(j2)
    assert job.job_id == j2 and job.state == JobState.RUNNING
    assert store.claim(j2) is None          # not claimable while RUNNING
    assert store.get(j1).state == JobState.ENQUEUED
    # a second launcher sharing the db on disk cannot double-claim
    other = JobStore(str(tmp_path / "jobs.db"))
    assert other.claim(j2) is None
    assert other.claim(j1).job_id == j1
    assert store.claim(j1) is None


# -- end-to-end service --------------------------------------------------------


def _make_batch(requests, max_batch=4):
    q = AdmissionQueue()
    b = MicroBatcher(q, max_batch=max_batch, max_wait_s=0.0)
    for r in requests:
        q.submit(r)
    batches = b.poll()
    assert len(batches) == 1
    return batches[0]


@pytest.mark.parametrize("executor", [EXECUTOR_JAX_REF, EXECUTOR_PALLAS,
                                      EXECUTOR_NUMPY_MT])
def test_batch_dbscan_matches_oracle_per_executor(tmp_path, executor):
    datasets = [blob(i, clusters=3, points=24) for i in (1, 2)]
    batch = _make_batch([
        req(tenant=f"t{i}", data=d, executor=executor)
        for i, d in enumerate(datasets)
    ])
    out = BatchExecutor(str(tmp_path)).run_batch(batch)
    assert not out.suspended and out.executor == executor
    for d, r in zip(datasets, out.results):
        oracle = dbscan.fit_oracle(d, DB_CFG)
        assert (r["labels"] == oracle).all()
        assert r["n_clusters"] == int(oracle.max(initial=0))


@pytest.mark.parametrize("executor", [EXECUTOR_JAX_REF, EXECUTOR_NUMPY_MT])
def test_batch_kmeans_matches_core_per_executor(tmp_path, executor):
    data = blob(7, clusters=4, points=48)
    batch = _make_batch([req(algo="kmeans", data=data,
                             params={"k": 4, "seed": 3},
                             executor=executor)])
    out = BatchExecutor(str(tmp_path)).run_batch(batch)
    assert not out.suspended
    r = out.results[0]
    ref = kmeans.fit_cancellable(
        jax.random.PRNGKey(3), jnp.asarray(data),
        kmeans.KMeansConfig(k=4, use_kernel=False))
    assert r["converged"] and bool(ref.converged)
    assert r["inertia"] == pytest.approx(float(ref.inertia), rel=1e-4)
    assert (r["labels"] == np.asarray(ref.labels)).all()


def test_submit_rejects_unhashable_params(tmp_path):
    """Unhashable param values must bounce at the door — inside the worker
    they would kill the serving loop while forming the batch key."""
    with ClusteringService(str(tmp_path)) as svc:
        with pytest.raises(ValueError, match="hashable"):
            svc.submit("t", "kmeans", blob(1),
                       params={"k": 4, "weights": [1, 2]})


def test_dbscan_padding_with_min_pts_one_has_no_phantom_clusters(tmp_path):
    """min_pts=1 makes every real point core; isolated pad rows must not
    seed phantom singleton clusters (they'd skew ids and can overflow)."""
    d1, d2 = blob(1, points=16), blob(2, points=8)   # unequal -> padding
    params = {"eps": DB_CFG.eps, "min_pts": 1}
    batch = _make_batch([
        req(data=d1, params=params, executor=EXECUTOR_JAX_REF),
        req(tenant="u", data=d2, params=params, executor=EXECUTOR_JAX_REF),
    ])
    out = BatchExecutor(str(tmp_path)).run_batch(batch)
    cfg1 = dbscan.DBSCANConfig(eps=DB_CFG.eps, min_pts=1)
    for d, r in zip((d1, d2), out.results):
        oracle = dbscan.fit_oracle(d, cfg1)
        assert (r["labels"] == oracle).all()
        assert r["n_clusters"] == int(oracle.max(initial=0))


def test_service_end_to_end_multi_tenant(tmp_path):
    datasets = {i: blob(i, clusters=3, points=24) for i in range(3)}
    with ClusteringService(str(tmp_path), max_batch=4,
                           max_wait_s=0.005) as svc:
        handles = [
            svc.submit(f"tenant-{i % 2}", "dbscan", d, params=DB_PARAMS)
            for i, d in datasets.items()
        ]
        km = svc.submit("tenant-0", "kmeans", datasets[0],
                        params={"k": 3, "seed": 1})
        for i, h in enumerate(handles):
            labels = h.wait(300)["labels"]
            assert (labels == dbscan.fit_oracle(datasets[i], DB_CFG)).all()
        assert km.wait(300)["iterations"] >= 1
        # duplicate submission: served from the cache, no recompute
        dup = svc.submit("tenant-9", "dbscan", datasets[0], params=DB_PARAMS)
        assert dup.cache_hit and dup.wait(5)["n_clusters"] >= 1
    snap = svc.metrics_snapshot()
    assert snap["requests"] == 5 and snap["cache_hits"] == 1
    assert snap["batches"] >= 1
    assert 0.0 < snap["mean_occupancy"] <= 1.0


# -- preemption + crash resume (the acceptance path) ---------------------------


def test_preempt_mid_batch_then_resume(tmp_path):
    """Kill the service mid-batch (cooperative preemption), restart, and the
    SUSPENDED batch resumes from its checkpoint to correct labels."""
    datasets = [blob(40 + i, clusters=8, points=64) for i in range(2)]
    oracles = [dbscan.fit_oracle(d, DB_CFG) for d in datasets]
    batch = _make_batch([
        req(tenant=f"t{i}", data=d, executor=EXECUTOR_JAX_REF)
        for i, d in enumerate(datasets)
    ])
    ex = BatchExecutor(str(tmp_path), checkpoint_every=2)
    token = CancellationToken()

    def hook(job_id, item, events):
        if events == 3:   # mid-batch, mid-item
            token.cancel(CancelReason.PREEMPTION)

    out = ex.run_batch(batch, token=token, progress_hook=hook)
    assert out.suspended
    job = ex.jobs.get(out.job_id)
    assert job.state == JobState.SUSPENDED
    assert job.checkpoint_path and os.path.exists(job.checkpoint_path)

    # "restart": a fresh executor over the same workdir
    ex2 = BatchExecutor(str(tmp_path), checkpoint_every=2)
    outcomes = ex2.resume_suspended()
    assert len(outcomes) == 1 and not outcomes[0].suspended
    assert outcomes[0].resumed
    for oracle, r in zip(oracles, outcomes[0].results):
        assert (r["labels"] == oracle).all()
    assert ex2.jobs.get(out.job_id).state == JobState.SUCCEEDED


def test_crash_with_stale_heartbeat_resumes_from_checkpoint(tmp_path):
    """A batch left RUNNING by a dead/stale owner is swept to SUSPENDED on
    restart and resumes from its checkpoint (core/jobs + checkpoint/store)."""
    data = blob(50, clusters=8, points=64)
    oracle = dbscan.fit_oracle(data, DB_CFG)
    batch = _make_batch([req(data=data, executor=EXECUTOR_JAX_REF)])
    ex = BatchExecutor(str(tmp_path), checkpoint_every=1,
                       heartbeat_timeout=0.05)
    token = CancellationToken()
    ex.run_batch(batch, token=token,
                 progress_hook=lambda j, i, e: e == 2 and token.cancel())
    jid = batch.requests[0].job_id
    # simulate a hard crash: the job looks RUNNING, heartbeat goes stale
    ex.jobs.claim(jid)
    time.sleep(0.1)
    ex2 = BatchExecutor(str(tmp_path), heartbeat_timeout=0.05)
    outcomes = ex2.resume_suspended()
    assert len(outcomes) == 1
    assert (outcomes[0].results[0]["labels"] == oracle).all()
    assert ex2.jobs.get(jid).state == JobState.SUCCEEDED


def test_service_level_preempt_raises_job_suspended(tmp_path):
    svc = ClusteringService(str(tmp_path), max_batch=1, max_wait_s=0.0,
                            checkpoint_every=1).start()
    h = svc.submit("t0", "dbscan", blob(60, clusters=8, points=128),
                   params=DB_PARAMS, executor=EXECUTOR_JAX_REF)
    deadline = time.time() + 30
    while h.job_id is None and time.time() < deadline:
        time.sleep(0.005)   # wait until the batch is durable (job formed)
    svc.stop(preempt=True)
    try:
        h.wait(1)
        finished_early = True
    except JobSuspended as e:
        finished_early = False
        assert e.job_id == h.job_id
    svc2 = ClusteringService(str(tmp_path))
    outcomes = svc2.resume_suspended()
    if finished_early:               # tiny machines may outrun the preempt
        assert outcomes == []
    else:
        assert len(outcomes) == 1 and not outcomes[0].suspended
    assert svc2.metrics_snapshot()["resumed_batches"] == len(outcomes)


_KILL_SCRIPT = r"""
import sys, time
sys.path.insert(0, {src!r})
import numpy as np, jax
from repro.data.synthetic import ClusterSpec, make_blobs
from repro.service import AdmissionQueue, MicroBatcher, BatchExecutor
from repro.service.queue import MiningRequest
from repro.core import dbscan

cfg = dbscan.DBSCANConfig.paper_defaults(2)
x, _, _ = make_blobs(jax.random.PRNGKey(77), ClusterSpec(2, 8, 64))
q = AdmissionQueue(); b = MicroBatcher(q, max_batch=2, max_wait_s=0.0)
q.submit(MiningRequest(tenant="t", algo="dbscan",
                       data=np.asarray(x, np.float32),
                       params={{"eps": cfg.eps, "min_pts": cfg.min_pts}},
                       executor="jax-ref"))
(batch,) = b.poll()
ex = BatchExecutor({workdir!r}, checkpoint_every=1)
# throttle so the parent reliably lands SIGKILL mid-batch
ex.run_batch(batch, progress_hook=lambda j, i, e: (print("EVT", e, flush=True),
                                                   time.sleep(0.25)))
print("FINISHED", flush=True)
"""


@pytest.mark.slow
def test_sigkill_subprocess_then_resume(tmp_path):
    """A real kill -9 mid-batch: the restarted executor sweeps the orphaned
    RUNNING job to SUSPENDED and completes it from the periodic checkpoint."""
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    workdir = str(tmp_path / "svc")
    script = _KILL_SCRIPT.format(src=src, workdir=workdir)
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True)
    saw_events = 0
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("EVT"):
                saw_events += 1
                if saw_events >= 2:   # >= 1 durable post-progress checkpoint
                    break
            if line.startswith("FINISHED") or not line:
                break
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(30)

    if saw_events < 2:
        pytest.skip("child finished before the kill landed")
    x, _, _ = make_blobs(jax.random.PRNGKey(77), ClusterSpec(2, 8, 64))
    oracle = dbscan.fit_oracle(np.asarray(x, np.float32), DB_CFG)
    ex = BatchExecutor(workdir)
    jobs = ex.jobs.list_jobs(JobState.RUNNING)
    assert len(jobs) == 1 and jobs[0].kind == SERVICE_JOB_KIND
    outcomes = ex.resume_suspended()
    assert len(outcomes) == 1 and not outcomes[0].suspended
    assert (outcomes[0].results[0]["labels"] == oracle).all()


def test_compile_failure_fails_the_request(tmp_path, monkeypatch):
    """A step program the compiler refuses fails the requests of its batch;
    no other program is swapped in to serve them."""
    from repro.service.exec_cache import ExecutableCache

    def refuse(self, n_pad, d, cfg):
        raise RuntimeError("compiler refused the K-Means step")

    monkeypatch.setattr(ExecutableCache, "_compile_kmeans", refuse)
    with ClusteringService(str(tmp_path), max_batch=1,
                           max_wait_s=0.0) as svc:
        h = MiningClient(service=svc).submit(
            "t", "kmeans", blob(3), params={"k": 4, "seed": 1},
            executor=EXECUTOR_JAX_REF)
        with pytest.raises(Exception, match="compiler refused"):
            h.result(120)
        assert "aot_failures" not in svc.exec_cache.stats()
