"""Serving sweep: offered load vs p50/p99 latency, occupancy, lane overlap.

Two axes of the perf trajectory:

1. **Throughput sweep** — for each paradigm executor and offered-load
   level, a fixed request population is submitted at the target arrival
   rate and the service's own metrics report per-request latency
   percentiles, mean batch occupancy, and the modeled energy spend (the
   ``benchmarks/energy.py`` model applied to batch runtimes).  The shape
   mirrors queueing intuition: higher offered load raises latency but also
   occupancy — the micro-batcher converts pressure into coalescing, the
   amortisation the paper buys with its single big GPU dispatch (Fig. 6).

2. **Lane overlap** — a mixed workload pinned half to ``numpy-mt`` and
   half to ``pallas-kernel`` runs through the executor pool.  With one
   queue + worker per paradigm, the lanes execute concurrently: total wall
   clock should be *less* than the sum of per-lane busy time.  A pool
   regression (everything serialising behind one lane) shows up as a
   starved lane or an overlap ratio <= 1.

3. **Distributed lane** — one request over a (deliberately tiny)
   per-device memory budget rides the load alongside normal requests.
   The cost model must route it to the ``distributed`` paradigm with NO
   caller opt-in, and its labels must match the single-device reference
   on the same data.  Run under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (as CI does) to
   exercise a real 4-way shard on CPU; exits nonzero if the oversized
   request never lands on the distributed lane or the labels diverge.

4. **Kill-and-replay gate** (``--recover-gate``) — a child process admits
   N requests (durable in the write-ahead admission log) without ever
   batching them, then dies to SIGKILL.  A fresh service over the same
   workdir runs ``recover()``; the gate exits nonzero if any admitted
   request fails to come back or its replayed labels diverge from an
   uninterrupted reference run — the "admitted means durable" contract,
   enforced in CI.

5. **Bucket-policy sweep** (``--bucket-sweep``) — replays three request
   *shape* workloads (uniform, zipf, bimodal point counts) through the
   service under each bucket policy (``pow2`` / ``linear:128`` /
   ``adaptive``) and emits the occupancy-vs-padding-vs-recompile table
   behind ``docs/bucketing_study.md``.  Doubles as the bucketing gate:
   exits nonzero if the adaptive policy fails to beat pow2 on padding
   waste for the zipf workload at an equal-or-better compiled-shape
   count.  The gate columns (``trace_*``) come from the policy applied
   to the workload trace itself — deterministic, no timing involved —
   while the service columns are the measured replay.

    PYTHONPATH=src python benchmarks/service_throughput.py            # fast
    PYTHONPATH=src python benchmarks/service_throughput.py --full
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python benchmarks/service_throughput.py --smoke  # CI
    PYTHONPATH=src python benchmarks/service_throughput.py --recover-gate
    PYTHONPATH=src python benchmarks/service_throughput.py --bucket-sweep
"""

from __future__ import annotations

import argparse
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List

# offered-load levels (requests/s) — low: batches mostly ride the deadline;
# high: the backlog keeps batches full
FAST_RATES = (50.0, 400.0)
FULL_RATES = (25.0, 100.0, 400.0, 1600.0)
SMOKE_RATES = (400.0,)
EXECUTORS = ("pallas-kernel", "jax-ref")

OVERLAP_LANES = ("numpy-mt", "pallas-kernel")


def run(fast: bool = True, smoke: bool = False) -> List[Dict]:
    from repro.launch.serve_mine import build_workload, drive
    from repro.service import ClusteringService, MiningClient

    if smoke:
        n_requests, rates, executors = 8, SMOKE_RATES, ("jax-ref",)
    else:
        n_requests = 24 if fast else 96
        rates = FAST_RATES if fast else FULL_RATES
        executors = EXECUTORS
    rows: List[Dict] = []
    for executor in executors:
        # per-executor warm-up workload shares jit compiles across rates
        for rate in rates:
            workdir = tempfile.mkdtemp(prefix="svc_bench_")
            try:
                service = ClusteringService(
                    workdir, max_batch=8, max_wait_s=0.01, cache_entries=0)
                client = MiningClient(service=service)
                workload = build_workload(
                    n_requests, tenants=4, algo="kmeans",
                    features=2, clusters=4, points=16,
                    seed=hash((executor, rate)) % 2**31)
                with service:
                    failures = drive(client, workload, rate, executor)
                snap = client.metrics()
                rows.append(dict(
                    executor=executor,
                    offered_rps=rate,
                    requests=snap["requests"],
                    p50_latency_s=snap["p50_latency_s"],
                    p99_latency_s=snap["p99_latency_s"],
                    mean_occupancy=snap["mean_occupancy"],
                    mean_batch_size=snap["mean_batch_size"],
                    batches=snap["batches"],
                    modeled_joules=snap["modeled_joules"],
                    failures=sum(failures.values()),
                ))
            finally:
                shutil.rmtree(workdir, ignore_errors=True)
    return rows


def run_overlap(smoke: bool = False) -> Dict:
    """Mixed numpy-mt + pallas-kernel load through the executor pool.

    Returns wall clock, per-lane busy seconds, and the overlap ratio
    (sum of lane busy time / wall).  Ratio > 1 means the lanes genuinely
    ran concurrently; each lane serving batches is the pool health check.
    """
    from repro.launch.serve_mine import build_workload
    from repro.service import ClusteringService, MiningClient

    n_requests = 8 if smoke else 24
    points = 24 if smoke else 64
    workdir = tempfile.mkdtemp(prefix="svc_overlap_")
    try:
        service = ClusteringService(
            workdir, max_batch=2, max_wait_s=0.002, cache_entries=0)
        client = MiningClient(service=service)
        workload = build_workload(
            n_requests, tenants=4, algo="kmeans",
            features=2, clusters=4, points=points, seed=7)
        with service:
            t0 = time.monotonic()
            handles = [
                client.submit(tenant, algo, data, params=params,
                              executor=OVERLAP_LANES[i % len(OVERLAP_LANES)])
                for i, (tenant, algo, data, params) in enumerate(workload)
            ]
            for h in handles:
                h.result(600)
            wall = time.monotonic() - t0
        snap = client.metrics()
        lanes = {
            name: st for name, st in snap["lanes"].items() if st["batches"]
        }
        busy = sum(st["busy_s"] for st in lanes.values())
        return {
            "requests": n_requests,
            "wall_s": wall,
            "busy_s": busy,
            "overlap_ratio": busy / wall if wall > 0 else 0.0,
            "lanes": {name: {"busy_s": st["busy_s"],
                             "batches": st["batches"]}
                      for name, st in lanes.items()},
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_distributed(smoke: bool = False) -> Dict:
    """Oversized request auto-routed to the distributed lane, end to end.

    A tiny device budget (64 KiB) makes a modest K-Means request
    "oversized", so the check runs in seconds on CPU while exercising the
    full path: admission -> singleton bypass batch -> distributed lane ->
    sharded execution -> labels identical to the single-device reference.
    Well-separated clusters keep the label comparison exact across
    reduction orders (1 vs N devices change all-reduce summation order).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kmeans
    from repro.service import ClusteringService, MiningClient

    budget = 64 * 1024   # ~49 KiB/1k pts for k=4 kmeans: n >= 2048 is over
    n = 2048 if smoke else 4096
    rng = np.random.default_rng(11)
    centers = np.array([[0.0, 0.0], [40.0, 0.0], [0.0, 40.0], [40.0, 40.0]],
                       np.float32)
    x = np.concatenate([
        c + rng.normal(0.0, 1.0, size=(n // 4, 2)).astype(np.float32)
        for c in centers
    ])
    rng.shuffle(x)
    seed = 99
    workdir = tempfile.mkdtemp(prefix="svc_dist_")
    try:
        service = ClusteringService(
            workdir, max_batch=4, max_wait_s=0.005, cache_entries=0,
            device_budget_bytes=budget)
        client = MiningClient(service=service)
        with service:
            small = [
                client.submit(f"t{i}", "kmeans",
                              x[i * 16:(i + 2) * 16],
                              params={"k": 2, "seed": i})
                for i in range(4)
            ]
            big = client.submit("big-tenant", "kmeans", x,
                                params={"k": 4, "seed": seed,
                                        "max_iters": 50})
            labels = big.result(600)["labels"]
            for h in small:
                h.result(600)
        snap = client.metrics()
        ref = kmeans.fit_cancellable(
            jax.random.PRNGKey(seed), jnp.asarray(x),
            kmeans.KMeansConfig(k=4, use_kernel=False, max_iters=50))
        dist_stats = snap["by_executor"].get("distributed", {})
        return {
            "devices": jax.device_count(),
            "n_points": int(x.shape[0]),
            "distributed_batches": int(dist_stats.get("batches", 0)),
            "labels_match": bool(
                (labels == np.asarray(ref.labels)).all()),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# -- bucket-policy sweep -------------------------------------------------------

# the swept policies; adaptive is instantiated per-workload with the same
# executable budget pow2 spends on that trace (an equal-cardinality
# comparison — see docs/bucketing_study.md)
BUCKET_WORKLOADS = ("uniform", "zipf", "bimodal")
BUCKET_POLICIES = ("pow2", "linear:128", "adaptive")


def _shape_trace(kind: str, count: int):
    """Per-workload request point counts (deterministic per kind)."""
    import numpy as np

    rng = np.random.default_rng({"uniform": 5, "zipf": 6, "bimodal": 7}[kind])
    if kind == "uniform":
        sizes = rng.integers(16, 1025, size=count)
    elif kind == "zipf":
        # heavy-tailed: most requests tiny, a fat tail of big ones — the
        # skewed multi-tenant mix where fixed pow2 pays the most padding
        sizes = np.clip(16 * rng.zipf(1.3, size=count), 16, 1536)
    elif kind == "bimodal":
        small = rng.normal(90.0, 10.0, size=count)
        large = rng.normal(820.0, 40.0, size=count)
        sizes = np.where(rng.random(count) < 0.8, small, large)
        sizes = np.clip(sizes, 16, 1024)
    else:
        raise ValueError(f"unknown shape workload {kind!r}")
    return [int(s) for s in sizes]


def run_bucket_sweep(smoke: bool = False):
    """Replay each shape workload under each bucket policy.

    Returns one row per (workload, policy): the deterministic trace-level
    padding/cardinality numbers the gate judges, plus the measured service
    replay (slot occupancy, point occupancy, recompiles, latency).
    """
    import numpy as np

    from repro.service import ClusteringService, MiningClient, make_policy
    from repro.service.bucketing import AdaptivePolicy, pow2_bucket

    count = 24 if smoke else 48
    rows = []
    for kind in BUCKET_WORKLOADS:
        sizes = _shape_trace(kind, count)
        rng = np.random.default_rng(
            {"uniform": 15, "zipf": 16, "bimodal": 17}[kind])
        datas = [rng.normal(0.0, 1.0, size=(n, 2)).astype(np.float32)
                 for n in sizes]
        pow2_shapes = len({pow2_bucket(n) for n in sizes})
        for spec in BUCKET_POLICIES:
            if spec == "adaptive":
                # same executable budget as pow2 spends on this trace:
                # the comparison is waste at equal cache cardinality
                policy = AdaptivePolicy(max_buckets=pow2_shapes)
                for n in sizes:
                    policy.observe(n)
                policy.refit()   # steady state a live service reaches
            else:
                policy = make_policy(spec)
            buckets = [policy.bucket(n) for n in sizes]
            trace_waste = 1.0 - sum(sizes) / sum(buckets)
            workdir = tempfile.mkdtemp(prefix="svc_bucket_")
            try:
                service = ClusteringService(
                    workdir, max_batch=4, max_wait_s=0.02,
                    cache_entries=0, wal=False, bucket_policy=policy)
                client = MiningClient(service=service)
                with service:
                    handles = [
                        client.submit(f"t{i % 4}", "kmeans", datas[i],
                                      params={"k": 4, "seed": 0,
                                              "max_iters": 8},
                                      executor="numpy-mt")
                        for i in range(count)
                    ]
                    for h in handles:
                        h.result(600)
                snap = client.metrics()
            finally:
                shutil.rmtree(workdir, ignore_errors=True)
            bkt = snap["bucketing"]
            rows.append(dict(
                workload=kind,
                policy=spec,
                requests=count,
                trace_waste=trace_waste,
                trace_buckets=len(set(buckets)),
                padding_waste=bkt["padding_waste"],
                point_occupancy=bkt["point_occupancy"],
                recompiles=bkt["recompiles"],
                mean_occupancy=snap["mean_occupancy"],
                batches=snap["batches"],
                p99_ms=snap["p99_latency_s"] * 1e3,
            ))
    return rows


def bucket_sweep_gate(rows) -> bool:
    """The acceptance bar: on the zipf workload, adaptive must beat pow2
    on padding waste without spending more compiled shapes."""
    zipf = {r["policy"]: r for r in rows if r["workload"] == "zipf"}
    ad, p2 = zipf["adaptive"], zipf["pow2"]
    ok = (ad["trace_waste"] < p2["trace_waste"]
          and ad["trace_buckets"] <= p2["trace_buckets"])
    if not ok:
        print(f"# FAIL: adaptive (waste {ad['trace_waste']:.3f}, "
              f"{ad['trace_buckets']} buckets) does not beat pow2 "
              f"(waste {p2['trace_waste']:.3f}, {p2['trace_buckets']} "
              f"buckets) on the zipf workload", file=sys.stderr)
    return ok


def _build_gate_workload(n: int):
    """Deterministic K-Means requests for the kill-and-replay gate.

    Pinned to jax-ref so the uninterrupted reference and the recovered
    replay run the identical code path (labels must match bit-for-bit).
    """
    import numpy as np

    rng = np.random.default_rng(23)
    out = []
    for i in range(n):
        centers = rng.uniform(-20.0, 20.0, size=(3, 2)).astype(np.float32)
        x = np.concatenate([
            c + rng.normal(0.0, 0.5, size=(24, 2)).astype(np.float32)
            for c in centers
        ])
        out.append((f"tenant-{i % 3}", "kmeans", x,
                    {"k": 3, "seed": 100 + i, "max_iters": 50}))
    return out


def _recover_child(workdir: str, n: int) -> None:
    """Gate child: admit N requests durably, signal readiness, then hang.

    The service is started but tuned so nothing ever batches (huge
    max_wait, max_batch > N): every request sits in the
    admission-to-batching window the WAL exists to protect.  The parent
    SIGKILLs this process once the marker file appears.
    """
    from repro.service import ClusteringService, MiningClient

    service = ClusteringService(workdir, max_batch=64, max_wait_s=3600.0)
    client = MiningClient(service=service)
    service.start()
    for tenant, algo, data, params in _build_gate_workload(n):
        client.submit(tenant, algo, data, params=params, executor="jax-ref")
    with open(os.path.join(workdir, "ADMITTED"), "w") as f:
        f.write(str(n))
    time.sleep(600)          # parent kills us long before this expires


def _reference_labels(workload, prefix: str) -> Dict[str, "np.ndarray"]:
    """Labels per content hash from an uninterrupted in-process run.

    Gates call this only after every child process that needs the device
    has exited: a chip belongs to one process at a time, and this one
    holds it from here on.
    """
    import numpy as np

    from repro.service import ClusteringService, MiningClient, content_key

    refdir = tempfile.mkdtemp(prefix=prefix)
    ref_labels: Dict[str, "np.ndarray"] = {}
    try:
        service = ClusteringService(refdir, max_batch=4, max_wait_s=0.005)
        client = MiningClient(service=service)
        with service:
            handles = [
                client.submit(tenant, algo, data, params=params,
                              executor="jax-ref")
                for tenant, algo, data, params in workload
            ]
            for (tenant, algo, data, params), h in zip(workload, handles):
                ref_labels[content_key(algo, params,
                                       np.asarray(data, np.float32))] = (
                    h.result(300)["labels"])
    finally:
        shutil.rmtree(refdir, ignore_errors=True)
    return ref_labels


def run_recover_gate(smoke: bool = False) -> Dict:
    """Kill-and-replay: SIGKILL a service with admitted-but-unbatched
    requests, recover over the same workdir, and demand zero losses.

    A child process admits N requests (durable in the WAL, never batched)
    and is killed with SIGKILL — no cleanup, no atexit, the admission
    queue dies in memory.  A fresh service over the same workdir runs
    ``recover()``: every request must come back through replay, complete,
    and produce labels identical to an uninterrupted reference run.
    """
    import numpy as np

    from repro.service import ClusteringService, MiningClient

    n = 4 if smoke else 8
    workload = _build_gate_workload(n)

    # crash run: child admits, parent SIGKILLs
    workdir = tempfile.mkdtemp(prefix="svc_recover_gate_")
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep + env.get("PYTHONPATH", ""))
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--recover-child", workdir, str(n)], env=env)
        marker = os.path.join(workdir, "ADMITTED")
        deadline = time.time() + 180
        try:
            while not os.path.exists(marker):
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"gate child exited early (rc={proc.returncode})")
                if time.time() > deadline:
                    raise RuntimeError("gate child never admitted")
                time.sleep(0.05)
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.wait(30)

        # this process starts JAX only now that the child, which needed
        # the device, is gone: the uninterrupted reference run first
        ref_labels = _reference_labels(workload, "svc_recover_ref_")

        # recovery run over the dead process's workdir.  Losses are
        # counted per workload item (did every expected content hash
        # produce labels?) — arithmetic over replayed/resumed counts can
        # double-cover a request that is both in a resumed batch and in a
        # WAL replay (kill between step-0 fsync and its CONSUME record)
        # and mask a real loss.
        service = ClusteringService(workdir, max_batch=4, max_wait_s=0.005)
        client = MiningClient(service=service)
        produced: Dict[str, "np.ndarray"] = {}
        with service:
            summary = client.recover()
            for o in summary["outcomes"]:
                if o.results and o.cache_keys:
                    for ck, res in zip(o.cache_keys, o.results):
                        produced[ck] = res["labels"]
            for h in summary["requests"]:
                try:
                    produced[h.cache_key] = h.result(300)["labels"]
                except Exception as e:
                    # surfaced in CI logs; the per-key loss count below
                    # still decides pass/fail
                    print(f"# replayed request {h.request_id} failed: "
                          f"{e!r}", file=sys.stderr)
        lost = mismatched = 0
        for ck, ref in ref_labels.items():
            got = produced.get(ck)
            if got is None:
                lost += 1
            elif not (got == ref).all():
                mismatched += 1
        pending = service.wal.pending() if service.wal is not None else -1
        return {
            "admitted": n,
            "replayed": summary["replayed"],
            "resumed_batches": summary["resumed_batches"],
            "cache_hits": summary["cache_hits"],
            "lost": lost,
            "mismatched": mismatched,
            "wal_pending_after": pending,
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_telemetry_gate(smoke: bool = False) -> Dict:
    """Telemetry gate: scrape a live exporter and demand a coherent story.

    Runs a short mixed workload through a real service with the HTTP
    exporter attached, then checks (a) the /metrics exposition parses
    cleanly, (b) the required series exist — per-stage latency for the
    execute and wal_append stages, per-executor modeled joules, and both
    SLO burn rates, (c) every request's exported trace contains the full
    span chain from WAL append through delivery, and (d) the span ring
    dropped nothing.  Any hole exits the process nonzero in CI.
    """
    import urllib.request

    import numpy as np

    from repro.service import (
        ClusteringService,
        MiningClient,
        TelemetryServer,
        exposition_errors,
    )

    n = 8 if smoke else 16
    required_series = (
        'repro_stage_latency_seconds{executor="",quantile="p50",'
        'stage="execute"}',
        'repro_stage_latency_seconds{executor="",quantile="p50",'
        'stage="wal_append"}',
        "repro_executor_modeled_joules{",
        'repro_slo_burn_rate{slo="latency"}',
        'repro_slo_burn_rate{slo="errors"}',
    )
    required_spans = {"wal_append", "queue_wait", "execute", "deliver"}
    workdir = tempfile.mkdtemp(prefix="svc_telemetry_")
    try:
        service = ClusteringService(workdir, max_batch=4, max_wait_s=0.005)
        client = MiningClient(service=service)
        rng = np.random.default_rng(31)
        with service, TelemetryServer(service.metrics_snapshot,
                                      tracer=service.tracer) as exporter:
            handles = []
            for i in range(n):
                algo = ("kmeans", "dbscan")[i % 2]
                # distinct content per request: a cache hit would skip the
                # queue/execute spans the gate demands
                data = rng.normal(0.0, 1.0, size=(48 + i, 2)).astype(
                    np.float32)
                params = ({"k": 3, "seed": i, "max_iters": 10}
                          if algo == "kmeans"
                          else {"eps": 0.5, "min_pts": 4})
                handles.append(client.submit(
                    f"tenant-{i % 3}", algo, data, params=params,
                    executor="jax-ref"))
            for h in handles:
                h.result(300)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{exporter.port}/metrics",
                    timeout=30) as resp:
                text = resp.read().decode("utf-8")
            problems = [f"exposition: {e}"
                        for e in exposition_errors(text)]
            for needle in required_series:
                if needle not in text:
                    problems.append(f"missing series: {needle}")
            incomplete = 0
            for h in handles:
                names = {s["name"]
                         for s in service.export_trace(h.trace_id)}
                if not required_spans <= names:
                    incomplete += 1
                    problems.append(
                        f"trace {h.trace_id} incomplete: missing "
                        f"{sorted(required_spans - names)}")
            dropped = service.tracer.stats()["dropped"]
            if dropped:
                problems.append(f"span ring dropped {dropped} span(s)")
        return {
            "requests": n,
            "exposition_bytes": len(text),
            "incomplete_traces": incomplete,
            "dropped_spans": dropped,
            "problems": problems,
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_fleet_gate(smoke: bool = False) -> Dict:
    """Fleet kill-failover gate: SIGKILL one of three workers holding
    admitted-but-unbatched requests; a survivor must adopt its WAL.

    A 3-worker fleet runs behind the consistent-hash router.  The victim
    (worker-0) is configured to admit but never batch — its requests sit
    exactly in the window the WAL protects — while the survivors serve a
    mixed live load.  Victim tenants' requests are submitted in durable
    mode (the RPC ACKs at WAL fsync), then the victim is SIGKILLed.  The
    manager's failover makes a survivor replay the victim's WAL; every
    admitted request must resolve with labels identical to an
    uninterrupted single-process reference (per content hash — the same
    loss accounting as the single-process recover gate), victim tenants
    must re-place onto survivors, the victim's WAL must drain to zero
    pending, and the fleet ``/metrics`` exposition must validate with
    per-worker labeled series.
    """
    import urllib.request

    import numpy as np

    from repro.service import content_key, exposition_errors
    from repro.service.fleet import FleetRouter, WorkerManager
    from repro.service.wal import RequestLog

    n_victim = 3 if smoke else 6
    n_live = 3 if smoke else 6

    def make_data(i: int) -> "np.ndarray":
        rng = np.random.default_rng(1000 + i)
        centers = rng.uniform(-20.0, 20.0, size=(3, 2)).astype(np.float32)
        return np.concatenate([
            c + rng.normal(0.0, 0.5, size=(24, 2)).astype(np.float32)
            for c in centers
        ])

    datasets = [make_data(i) for i in range(n_victim + n_live)]
    all_params = [{"k": 3, "seed": 500 + i, "max_iters": 50}
                  for i in range(n_victim + n_live)]

    root = tempfile.mkdtemp(prefix="svc_fleet_gate_")
    manager = WorkerManager(
        root, 3,
        worker_config={"max_batch": 4, "max_wait_s": 0.005},
        # the victim admits but never batches: every one of its requests
        # sits in the admission-to-batching window the WAL protects
        overrides={"worker-0": {"max_batch": 64, "max_wait_s": 3600.0}},
        heartbeat_interval=0.25)
    manager.start()
    router = FleetRouter(manager)
    exporter = router.serve_metrics(0)
    problems: List[str] = []
    try:
        victim_tenants = [t for t in (f"tenant-{i}" for i in range(200))
                          if router.ring.primary(t) == "worker-0"
                          ][:n_victim]
        live_tenants = [t for t in (f"tenant-{i}" for i in range(200))
                        if router.ring.primary(t) != "worker-0"][:n_live]

        # durable admits on the victim first (sequential, so bounded-load
        # never spills them off their idle primary): ACK = WAL fsync
        victim_handles = []
        for i, tenant in enumerate(victim_tenants):
            h = router.submit(tenant, "kmeans", datasets[i],
                              params=all_params[i], executor="jax-ref",
                              durable=True)
            ack = h.admitted(60)
            victim_handles.append((h, ack))
        admitted_at_victim = sum(
            1 for _, ack in victim_handles if ack["worker"] == "worker-0")

        # mixed live load on the survivors, still in flight at the kill
        live_handles = [
            router.submit(t, "kmeans", datasets[n_victim + j],
                          params=all_params[n_victim + j],
                          executor="jax-ref")
            for j, t in enumerate(live_tenants)]

        manager.fail_worker("worker-0")   # SIGKILL + synchronous failover

        produced: Dict[str, "np.ndarray"] = {}
        for j, h in enumerate(live_handles):
            key = content_key("kmeans", all_params[n_victim + j],
                              datasets[n_victim + j])
            try:
                produced[key] = h.result(300)["labels"]
            except Exception as e:
                print(f"# live request {h.tenant} failed: {e!r}",
                      file=sys.stderr)
        for h, ack in victim_handles:
            try:
                produced[ack["cache_key"]] = h.result(300)["labels"]
            except Exception as e:
                print(f"# victim-admitted request {h.tenant} failed: "
                      f"{e!r}", file=sys.stderr)

        takeover = manager.takeovers[0] if manager.takeovers else {}
        replayed = int(takeover.get("replayed", 0))
        if replayed < max(1, admitted_at_victim):
            problems.append(
                f"takeover replayed {replayed} of {admitted_at_victim} "
                f"requests admitted at the victim")

        replaced = {t: router.place(t) for t in victim_tenants}
        if any(w == "worker-0" for w in replaced.values()):
            problems.append(f"victim tenants not re-placed: {replaced}")

        # the survivor's takeover must have drained the victim's log
        wal = RequestLog(os.path.join(root, "worker-0", "wal"))
        victim_pending = wal.pending()
        wal.close()
        if victim_pending:
            problems.append(
                f"victim WAL still has {victim_pending} pending admits")

        with urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics",
                timeout=30) as resp:
            text = resp.read().decode("utf-8")
        problems += [f"fleet exposition: {e}"
                     for e in exposition_errors(text)]
        for needle in (
                'repro_fleet_worker_up{worker="worker-0"} 0.0',
                'repro_fleet_worker_up{worker="worker-1"} 1.0',
                'repro_fleet_worker_up{worker="worker-2"} 1.0',
                'repro_fleet_worker_requests_total{worker="',
                'repro_fleet_takeover_replayed_total{',
                "repro_fleet_takeovers_total 1",
        ):
            if needle not in text:
                problems.append(f"missing fleet series: {needle}")
    finally:
        exporter.stop()
        router.close()
        manager.stop()
        shutil.rmtree(root, ignore_errors=True)

    # the workers, which needed the device, are gone: now this process
    # may start JAX for the uninterrupted single-process reference
    ref_labels = _reference_labels(
        [("ref", "kmeans", d, p) for d, p in zip(datasets, all_params)],
        "svc_fleet_ref_")
    lost = mismatched = 0
    for key, ref in ref_labels.items():
        got = produced.get(key)
        if got is None:
            lost += 1
        elif not (got == ref).all():
            mismatched += 1
    return {
        "admitted": n_victim + n_live,
        "admitted_at_victim": admitted_at_victim,
        "replayed": replayed,
        "adopter": takeover.get("adopter"),
        "lost": lost,
        "mismatched": mismatched,
        "victim_wal_pending": victim_pending,
        "replaced": replaced,
        "problems": problems,
    }


# -- continuous-batching speed gate -------------------------------------------

# one BatchKey for the whole convoy: every request must share the compiled
# program (and the pow2 bucket) or none of them could join the hot batch
SPEED_PARAMS = {"k": 32, "seed": 7, "max_iters": 400, "tol": 1e-12}
SPEED_DIMS = 8
# every convoy member has the SAME point count: centroid init runs on the
# unpadded slice (its semantics are pinned to the core fit by the
# service's numerics tests), so a distinct length is a distinct jitted
# init — one shared length keeps the gate about scheduling, not tracing
SPEED_POINTS = 16384


def _speed_blobs(n: int, k: int, d: int, seed: int):
    """Tight, well-separated blobs: Lloyd reaches its fixed point (shift
    exactly 0.0 < tol) within a few dozen iterations — the convoy's
    quick-converging "short" jobs."""
    import numpy as np

    rng = np.random.default_rng(seed)
    centers = rng.uniform(-50.0, 50.0, size=(k, d)).astype(np.float32)
    per = max(1, n // k)
    x = np.concatenate([
        c + rng.normal(0.0, 0.05, size=(per, d)).astype(np.float32)
        for c in centers
    ])
    x = np.concatenate([x, x[: n - x.shape[0]]]) if x.shape[0] < n else x[:n]
    rng.shuffle(x)
    return x


def _speed_workload(smoke: bool):
    """(long_x, shorts): one slow job + a trickle of quick ones.

    The long job is a structureless uniform cloud — k-means keeps
    shuffling boundary points for ~170 iterations before the assignments
    freeze — while every short is a tight blob mixture that converges in
    ~30.  Same params, same length, same pow2 bucket: the only difference
    is how long each takes, which is exactly the asymmetry continuous
    batching exploits (shorts retire early, new shorts join the freed
    slots)."""
    import numpy as np

    n_shorts = 8 if smoke else 12
    long_x = np.random.default_rng(5).uniform(
        -5.0, 5.0, size=(SPEED_POINTS, SPEED_DIMS)).astype(np.float32)
    shorts = [
        _speed_blobs(SPEED_POINTS, SPEED_PARAMS["k"], SPEED_DIMS, 30 + i)
        for i in range(n_shorts)
    ]
    return long_x, shorts


def _speed_run(continuous: bool, long_x, shorts, gap_s: float) -> Dict:
    """Drive the convoy through one service instance; return the scorecard.

    The timed section starts after a warm-up request with the convoy's own
    BatchKey and bucket, so both modes run on a hot executable and the
    measured margin is scheduling, not compilation."""
    import threading

    from repro.service import ClusteringService, MiningClient

    params = dict(SPEED_PARAMS)
    warm_spec = [dict(algo="kmeans", features=SPEED_DIMS,
                      n=int(long_x.shape[0]), executor="jax-ref", **params)]
    workdir = tempfile.mkdtemp(prefix="svc_speed_")
    try:
        # max_wait_s is a *realistic* coalescing window — batch-at-a-time
        # pays it per formed batch, while continuous joins claim staged
        # requests at the next iteration boundary without ripening first:
        # that bypass is precisely the scheduling win under measurement
        service = ClusteringService(
            workdir, max_batch=4, max_wait_s=0.25,
            continuous=continuous, warm_start=warm_spec,
            bucket_policy="pow2", cache_entries=0, checkpoint_every=64)
        # hold ripe shorts a little longer for the hot batch's boundary
        service.batcher.join_defer_s = 0.6
        client = MiningClient(service=service)
        done_at: Dict[str, float] = {}
        threads = []

        def _track(name, handle):
            def _wait():
                handle.result(600)
                done_at[name] = time.monotonic()
            t = threading.Thread(target=_wait, daemon=True)
            t.start()
            threads.append(t)

        with service:
            client.submit("warm", "kmeans",
                          _speed_blobs(int(long_x.shape[0]), params["k"],
                                       SPEED_DIMS, 999),
                          params=params, executor="jax-ref").result(600)
            # the retire path resolves futures BEFORE the batch is
            # absorbed into the metrics: wait for the warm batch's
            # counters so the after-warm-up deltas start from a settled
            # baseline
            deadline = time.monotonic() + 10
            while (service.metrics_snapshot()["batches"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            warm = service.metrics_snapshot()
            t0 = time.monotonic()
            _track("long", client.submit("convoy", "kmeans", long_x,
                                         params=params, executor="jax-ref"))
            for i, x in enumerate(shorts):
                time.sleep(gap_s)
                _track(f"short{i}",
                       client.submit("convoy", "kmeans", x, params=params,
                                     executor="jax-ref"))
            for t in threads:
                t.join(600)
            wall = max(done_at.values()) - t0
        snap = service.metrics_snapshot()
        points = int(long_x.shape[0]) + sum(int(x.shape[0]) for x in shorts)
        short_done = [v for k, v in done_at.items() if k.startswith("short")]
        return {
            "mode": "continuous" if continuous else "batch",
            "wall_s": wall,
            "points": points,
            "pps": points / wall if wall > 0 else 0.0,
            "joins": snap["continuous"]["joins"],
            "early_retires": snap["continuous"]["early_retires"],
            "continuous_batches": snap["continuous"]["batches"],
            "mean_slot_occupancy":
                snap["continuous"]["mean_slot_occupancy"],
            "batches": snap["batches"],
            "recompiles_after_warm": (snap["bucketing"]["recompiles"]
                                      - warm["bucketing"]["recompiles"]),
            "exec_misses_after_warm": (snap["exec_cache"]["misses"]
                                       - warm["exec_cache"]["misses"]),
            "exec_cache": snap["exec_cache"],
            "short_before_long": bool(
                short_done and "long" in done_at
                and min(short_done) < done_at["long"]),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_speed_gate(smoke: bool = False) -> Dict:
    """Continuous vs batch-at-a-time on the convoy trace.

    Each mode runs twice in alternating order (continuous first and last,
    so slow-drift effects — page cache, CPU thermal state, the
    process-wide executable cache warming — cancel instead of favouring
    one side) and the faster trial represents the mode, the standard
    min-of-N defence against host timing noise.  The gate demands:
    continuous strictly beats batch-at-a-time on points/sec, at least one
    join and one early retire actually happened, a short resolved before
    the long job, and the warm continuous run compiled nothing new (zero
    recompiles, zero executable-cache misses after warm-up)."""
    long_x, shorts = _speed_workload(smoke)
    gap = 0.15
    conts = [_speed_run(True, long_x, shorts, gap)]
    batches = [_speed_run(False, long_x, shorts, gap),
               _speed_run(False, long_x, shorts, gap)]
    conts.append(_speed_run(True, long_x, shorts, gap))
    cont = min(conts, key=lambda r: r["wall_s"])
    batch = min(batches, key=lambda r: r["wall_s"])
    problems: List[str] = []
    if cont["pps"] <= batch["pps"]:
        problems.append(
            f"continuous {cont['pps']:.0f} pps does not beat "
            f"batch-at-a-time {batch['pps']:.0f} pps")
    if cont["joins"] < 1:
        problems.append("no queued request ever joined the in-flight batch")
    if cont["early_retires"] < 1:
        problems.append("no item retired before its batch ended")
    if not cont["short_before_long"]:
        problems.append("no short job resolved before the long job")
    if cont["recompiles_after_warm"] > 0:
        problems.append(
            f"{cont['recompiles_after_warm']} recompile(s) after warm-up")
    if cont["exec_misses_after_warm"] > 0:
        problems.append(
            f"{cont['exec_misses_after_warm']} executable-cache miss(es) "
            f"after warm-up")
    return {"continuous": cont, "batch": batch, "problems": problems}


def run_energy_gate(smoke: bool = False) -> Dict:
    """Energy gate: the same trace uncapped then under a power cap.

    Three contracts, all CI-enforced:

    1. **The cap holds.**  The capped replay's pacer-charged joules over
       its wall clock must stay at or under the cap wattage (plus the
       bucket's initial burst, amortised over the run), and the pacer
       must have actually throttled at least once — a cap that never
       bites proves nothing.
    2. **Energy does not regress.**  Pacing stalls dispatch, so queued
       requests coalesce into fuller batches; modeled joules per real
       point must not grow past the uncapped baseline (small tolerance
       for host timing noise).
    3. **Budgets reject honestly.**  A tenant that overdraws its joule
       budget gets ``EnergyBudgetExceeded`` with a positive, bounded
       ``retry_after`` — and a resubmit after waiting it out is
       admitted.

    The capped run's ``/metrics`` exposition must also parse cleanly and
    carry the ``repro_energy_*`` family.
    """
    import numpy as np

    from repro.service import ClusteringService, MiningClient
    from repro.service.queue import EnergyBudgetExceeded
    from repro.service.telemetry import exposition_errors, render_prometheus

    n = 8 if smoke else 16
    rng = np.random.default_rng(97)
    trace = [rng.normal(0.0, 1.0, size=(192 + 16 * i, 2)).astype(np.float32)
             for i in range(n)]
    problems: List[str] = []

    def replay(power_cap):
        # batch-at-a-time on purpose: continuous joins enter an in-flight
        # batch without passing the dispatch pacer, so a capped replay
        # with joining would be unpaced for most of its requests
        workdir = tempfile.mkdtemp(prefix="svc_energy_")
        try:
            service = ClusteringService(
                workdir, max_batch=4, max_wait_s=0.005, cache_entries=0,
                continuous=False,
                power_cap_watts=power_cap,
                power_cap_burst_joules=(None if power_cap is None
                                        else power_cap * 0.25))
            client = MiningClient(service=service)
            t0 = time.monotonic()
            with service:
                handles = []
                for i, x in enumerate(trace):
                    # trickle the trace in: uncapped, each request mostly
                    # rides its own small batch; under the cap the stalled
                    # lane lets the queue coalesce fuller batches — the
                    # joules/point win the gate demands
                    handles.append(client.submit(
                        f"tenant-{i % 3}", "kmeans", x,
                        params={"k": 4, "seed": i, "max_iters": 10},
                        executor="jax-ref"))
                    time.sleep(0.02)
                for h in handles:
                    h.result(300)
                wall = time.monotonic() - t0
            # snapshot after stop(): batch records (and their joules)
            # land once the lanes drain
            snap = service.metrics_snapshot()
            text = render_prometheus(snap)
            return wall, snap, text
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    replay(None)             # warm-up: pay the one-time jit compiles
    wall_u, snap_u, _ = replay(None)
    energy_u = snap_u["energy"]
    draw_u = energy_u["joules_total"] / max(wall_u, 1e-9)
    # well under the uncapped draw, so the pacer must bite
    cap_watts = max(draw_u * 0.4, 1e-3)
    wall_c, snap_c, text = replay(cap_watts)
    energy_c = snap_c["energy"]
    cap = energy_c.get("cap") or {}

    burst = float(cap.get("burst_joules") or 0.0)
    paced_draw = cap.get("spent_joules", 0.0) / max(wall_c, 1e-9)
    allowed = cap_watts * 1.05 + burst / max(wall_c, 1e-9)
    if paced_draw > allowed:
        problems.append(
            f"capped run drew {paced_draw:.4f} W (pacer-charged) against "
            f"a {cap_watts:.4f} W cap (+{burst:.3f} J burst)")
    if not cap.get("throttles"):
        problems.append("the power cap never throttled a batch — "
                        "the capped replay proves nothing")
    jpp_u = energy_u.get("joules_per_point", 0.0)
    jpp_c = energy_c.get("joules_per_point", 0.0)
    if jpp_u <= 0.0:
        problems.append("uncapped run recorded zero joules per point")
    elif jpp_c > jpp_u * 1.05:
        problems.append(
            f"joules/point regressed under the cap: "
            f"{jpp_c * 1e3:.4f} mJ vs {jpp_u * 1e3:.4f} mJ uncapped")

    # -- per-tenant joule budget: honest rejection + honest retry_after --
    rate, burst_j = 0.05, 0.05
    workdir = tempfile.mkdtemp(prefix="svc_energy_budget_")
    try:
        service = ClusteringService(
            workdir, max_batch=4, max_wait_s=0.005, cache_entries=0,
            tenant_joule_rate=rate, tenant_joule_burst=burst_j)
        client = MiningClient(service=service)
        payload = [rng.normal(0.0, 1.0, size=(4096, 2)).astype(np.float32)
                   for _ in range(3)]
        params = {"k": 8, "max_iters": 5}
        rejected = None
        with service:
            first = client.submit("hog", "kmeans", payload[0],
                                  params=dict(params, seed=0),
                                  executor="numpy-mt")
            try:
                client.submit("hog", "kmeans", payload[1],
                              params=dict(params, seed=1),
                              executor="numpy-mt")
            except EnergyBudgetExceeded as exc:
                rejected = exc
            if rejected is None:
                problems.append("over-budget tenant was admitted")
            else:
                # retry_after must be positive and bounded by the worst
                # case (empty bucket + full debt): (need + burst) / rate
                worst = (min(rejected.needed_joules, burst_j)
                         + burst_j) / rate
                if not (0.0 < rejected.retry_after <= worst + 1e-6):
                    problems.append(
                        f"retry_after {rejected.retry_after!r} outside "
                        f"(0, {worst:.2f}]")
                if rejected.needed_joules <= 0.0:
                    problems.append(
                        f"rejection priced at "
                        f"{rejected.needed_joules!r} J")
                time.sleep(rejected.retry_after + 0.05)
                retried = client.submit("hog", "kmeans", payload[2],
                                        params=dict(params, seed=2),
                                        executor="numpy-mt")
                retried.result(300)
            first.result(300)
            rejections = service.metrics_snapshot()[
                "energy"]["budget"]["rejections"]
        if rejected is not None and rejections < 1:
            problems.append("rejection not counted in "
                            "energy.budget.rejections")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    problems.extend(f"exposition: {e}" for e in exposition_errors(text))
    for needle in ("repro_energy_modeled_watts",
                   "repro_energy_power_cap_watts",
                   "repro_energy_joules_total",
                   "repro_energy_cap_throttle_seconds_total",
                   "repro_energy_budget_rejections_total",
                   'repro_energy_class_joules_total{device_class="big"}'):
        if needle not in text:
            problems.append(f"missing series: {needle}")

    return {
        "requests": n,
        "uncapped": {"wall_s": wall_u, "draw_w": draw_u,
                     "joules_per_point": jpp_u,
                     "joules_total": energy_u.get("joules_total", 0.0)},
        "capped": {"wall_s": wall_c, "cap_watts": cap_watts,
                   "paced_draw_w": paced_draw,
                   "joules_per_point": jpp_c,
                   "throttles": cap.get("throttles", 0),
                   "throttled_s": cap.get("throttled_s_total", 0.0)},
        "budget_retry_after": (rejected.retry_after
                               if rejected is not None else None),
        "problems": problems,
    }


# -- zero-downtime standby gate -----------------------------------------------


def _standby_child(workdir: str, n: int, port: int) -> None:
    """Gate child: admit N requests durably while shipping the WAL to a
    standby at ``port``, signal readiness, then hang until SIGKILLed.

    Tuned so nothing ever batches (the admission-to-batching window the
    WAL protects); the shipper runs on a tight cadence so the standby
    converges while the child is still alive.
    """
    from repro.service import ClusteringService, MiningClient
    from repro.service.replicate import WalShipper

    service = ClusteringService(workdir, max_batch=64, max_wait_s=3600.0)
    client = MiningClient(service=service)
    service.start()
    shipper = WalShipper(service.wal, "127.0.0.1", port,
                         interval=0.02).start()
    service.attach_replicator(shipper)
    for tenant, algo, data, params in _build_gate_workload(n):
        client.submit(tenant, algo, data, params=params, executor="jax-ref")
    with open(os.path.join(workdir, "ADMITTED"), "w") as f:
        f.write(str(n))
    time.sleep(600)          # parent kills us long before this expires


def run_standby_gate(smoke: bool = False) -> Dict:
    """Zero-downtime gate: warm-standby promotion + fleet rolling restart.

    Phase 1 — promotion.  A child process admits N durable requests while
    a :class:`WalShipper` mirrors its WAL to a parent-hosted
    :class:`StandbyReplica` under live load.  Once the standby reports
    zero lag the child is SIGKILLed — primary and workdir both "lost" —
    and the standby is promoted.  Every admitted request must resolve
    through the promoted service's replay with labels identical to an
    uninterrupted reference (per content hash), the replica's
    ``repro_replica_*`` exposition must validate, and a live config
    reload on the promoted service must land at a fresh epoch in both
    the snapshot and the exposition.

    Phase 2 — rolling restart.  A 2-worker fleet serves durably-admitted
    requests while ``WorkerManager.rolling_restart()`` drains and
    respawns every worker one at a time; all handles must resolve with
    reference-identical labels, every worker pid must change, and a
    fleet-wide ``/reload`` must converge on one epoch on every worker.

    This process starts JAX (promotion, reference run) only after the
    child and the fleet workers, which need the device, have exited.
    """
    import urllib.request

    import numpy as np

    from repro.service import StandbyReplica, content_key, exposition_errors
    from repro.service.fleet import FleetRouter, WorkerManager
    from repro.service.telemetry import render_prometheus

    n = 4 if smoke else 8
    workload = _build_gate_workload(n)
    problems: List[str] = []

    # -- phase 1a: ship under live load, SIGKILL -----------------------------
    primary_dir = tempfile.mkdtemp(prefix="svc_standby_primary_")
    standby_dir = tempfile.mkdtemp(prefix="svc_standby_mirror_")
    standby = StandbyReplica(standby_dir).start()
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", ""))
    lag_at_kill = None
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--standby-child", primary_dir, str(n), str(standby.port)],
            env=env)
        marker = os.path.join(primary_dir, "ADMITTED")
        deadline = time.time() + 180
        try:
            while not os.path.exists(marker):
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"gate child exited early (rc={proc.returncode})")
                if time.time() > deadline:
                    raise RuntimeError("gate child never admitted")
                time.sleep(0.05)
            # the standby must converge while the primary still lives
            while time.time() < deadline:
                snap = standby.stats()
                if (snap["pending_entries"] >= n
                        and snap["lag_entries"] == 0):
                    break
                time.sleep(0.05)
            lag_at_kill = standby.stats()["lag_entries"]
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.wait(30)
        if lag_at_kill != 0:
            problems.append(
                f"standby never caught up before the kill "
                f"(lag {lag_at_kill} entries)")

        # the replica exposition must validate while it is still a standby
        with urllib.request.urlopen(
                f"http://127.0.0.1:{standby.port}/metrics",
                timeout=30) as resp:
            replica_text = resp.read().decode("utf-8")
        problems += [f"replica exposition: {e}"
                     for e in exposition_errors(replica_text)]
        for needle in ("repro_replica_lag_entries",
                       "repro_replica_pending_entries",
                       "repro_replica_applies_total",
                       "repro_replica_ok 1"):
            if needle not in replica_text:
                problems.append(f"missing replica series: {needle}")

        # -- phase 2: fleet rolling restart under durable load ---------------
        root = tempfile.mkdtemp(prefix="svc_standby_fleet_")
        manager = WorkerManager(
            root, 2, worker_config={"max_batch": 4, "max_wait_s": 0.05},
            heartbeat_interval=0.25)
        manager.start()
        router = FleetRouter(manager)
        rolled: Dict[str, "np.ndarray"] = {}
        roll_failed = 0
        restarted_pids = {}
        reload_epochs = {}
        try:
            # fleet-wide live reload first: every worker must land on
            # epoch 1
            reload_result = router.reload({"tenant_rate": 77.0})
            reload_epochs = reload_result["epochs"]
            if not reload_result["converged"]:
                problems.append(
                    f"fleet reload did not converge: epochs "
                    f"{reload_result['epochs']}, errors "
                    f"{reload_result['errors']}")
            elif set(reload_result["epochs"].values()) != {1}:
                problems.append(
                    f"stale config epoch after fleet reload: "
                    f"{reload_result['epochs']}")

            before_pids = {name: spec.proc.pid
                           for name, spec in manager.workers.items()}
            handles = []
            for tenant, algo, data, params in workload:
                h = router.submit(tenant, algo, data, params=params,
                                  executor="jax-ref", durable=True)
                h.admitted(60)
                handles.append(h)

            manager.rolling_restart(drain_timeout=60.0)

            for (tenant, algo, data, params), h in zip(workload, handles):
                key = content_key(algo, params,
                                  np.asarray(data, np.float32))
                try:
                    rolled[key] = h.result(300)["labels"]
                except Exception as e:
                    print(f"# rolled request {tenant} failed: {e!r}",
                          file=sys.stderr)
                    roll_failed += 1

            restarted_pids = {name: spec.proc.pid
                              for name, spec in manager.workers.items()}
            stuck = [name for name, pid in restarted_pids.items()
                     if before_pids.get(name) == pid]
            if stuck:
                problems.append(f"rolling restart left old pids: {stuck}")
            if len(manager.restarts) != len(before_pids):
                problems.append(
                    f"expected {len(before_pids)} restart records, got "
                    f"{len(manager.restarts)}")
            # the upgraded fleet still serves
            tenant, algo, data, params = workload[0]
            post = router.submit(tenant, algo, data,
                                 params=dict(params, seed=9999),
                                 executor="jax-ref")
            post.result(300)
        finally:
            router.close()
            manager.stop()
            shutil.rmtree(root, ignore_errors=True)

        # -- phase 1b: promote the standby (JAX starts in this process) ------
        svc, summary = standby.promote(max_batch=4, max_wait_s=0.005)
        produced: Dict[str, "np.ndarray"] = {}
        try:
            for r in summary["requests"]:
                try:
                    produced[r.cache_key] = r.wait(300)["labels"]
                except Exception as e:
                    print(f"# promoted replay {r.request_id} failed: "
                          f"{e!r}", file=sys.stderr)
            promoted_pending = (svc.wal.pending()
                                if svc.wal is not None else -1)
            # live reload on the promoted service: the epoch must move
            # and be visible in snapshot AND exposition (stale = fail)
            svc.apply_config({"tenant_rate": 50.0})
            msnap = svc.metrics_snapshot()
            if msnap["config"]["epoch"] != 1:
                problems.append(
                    f"stale config epoch after reload: snapshot says "
                    f"{msnap['config']['epoch']}, expected 1")
            if "repro_config_epoch 1" not in render_prometheus(msnap):
                problems.append(
                    "stale config epoch after reload: exposition still "
                    "lacks repro_config_epoch 1")
        finally:
            svc.stop(drain=True)
        if promoted_pending:
            problems.append(f"promoted WAL still has {promoted_pending} "
                            f"pending admits")
    finally:
        standby.stop()
        shutil.rmtree(primary_dir, ignore_errors=True)
        shutil.rmtree(standby_dir, ignore_errors=True)

    ref_labels = _reference_labels(workload, "svc_standby_ref_")
    lost = mismatched = 0
    roll_mismatched = 0
    for ck, ref in ref_labels.items():
        got = produced.get(ck)
        if got is None:
            lost += 1
        elif not (got == ref).all():
            mismatched += 1
        got = rolled.get(ck)
        if got is not None and not (got == ref).all():
            roll_mismatched += 1

    return {
        "admitted": n,
        "replayed": summary["replayed"],
        "lost": lost,
        "mismatched": mismatched,
        "lag_at_kill": lag_at_kill,
        "promoted_pending": promoted_pending,
        "reload_epochs": reload_epochs,
        "rolled": len(workload),
        "roll_lost": roll_failed,
        "roll_mismatched": roll_mismatched,
        "restarts": len(restarted_pids),
        "problems": problems,
    }


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface (separate so the docs gate can introspect it)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI load: one sweep point + lane overlap; "
                         "exits nonzero if a pool lane is starved")
    ap.add_argument("--recover-gate", action="store_true",
                    help="run ONLY the kill-and-replay durability gate: "
                         "SIGKILL a service with admitted-but-unbatched "
                         "requests, recover(), exit nonzero on any lost "
                         "request or label mismatch")
    ap.add_argument("--bucket-sweep", action="store_true",
                    help="run ONLY the bucket-policy sweep: replay "
                         "uniform/zipf/bimodal shape workloads under "
                         "pow2/linear/adaptive bucketing and exit nonzero "
                         "if adaptive fails to beat pow2 on padding waste "
                         "for zipf at equal-or-better recompile count")
    ap.add_argument("--telemetry-gate", action="store_true",
                    help="run ONLY the telemetry gate: drive a short mixed "
                         "workload with the HTTP exporter attached, scrape "
                         "/metrics, and exit nonzero on malformed "
                         "exposition, a missing required series (per-stage "
                         "latency, per-executor joules, SLO burn rate), an "
                         "incomplete request trace, or dropped spans")
    ap.add_argument("--fleet-gate", action="store_true",
                    help="run ONLY the fleet failover gate: 3-worker fleet "
                         "behind the consistent-hash router, SIGKILL one "
                         "worker holding durably-admitted requests "
                         "mid-batch, exit nonzero if the surviving "
                         "workers lose any admitted request, produce "
                         "labels differing from an uninterrupted "
                         "reference, fail to re-place the victim's "
                         "tenants, or emit a malformed fleet /metrics "
                         "exposition")
    ap.add_argument("--speed-gate", action="store_true",
                    help="run ONLY the continuous-batching speed gate: a "
                         "convoy trace (one slow K-Means job + a trickle "
                         "of quick ones, same compiled program) through "
                         "continuous and batch-at-a-time services; exit "
                         "nonzero unless continuous wins on points/sec "
                         "with at least one join and one early retire and "
                         "ZERO recompiles or executable-cache misses "
                         "after warm-up")
    ap.add_argument("--energy-gate", action="store_true",
                    help="run ONLY the energy gate: replay the same trace "
                         "uncapped and under a power cap; exit nonzero "
                         "unless the capped run's pacer-charged draw "
                         "stays at or under the cap with at least one "
                         "throttle, joules/point does not regress, an "
                         "over-budget tenant is rejected with a valid "
                         "retry_after, and the repro_energy_* exposition "
                         "validates")
    ap.add_argument("--standby-gate", action="store_true",
                    help="run ONLY the zero-downtime gate: ship the WAL "
                         "to a warm standby under live load, SIGKILL the "
                         "primary, promote the standby, and roll-restart "
                         "a 2-worker fleet holding durable admits; exit "
                         "nonzero on any lost admitted request (per "
                         "content hash), a stale config epoch after a "
                         "live reload, or an invalid repro_replica_* "
                         "exposition")
    ap.add_argument("--recover-child", nargs=2, metavar=("WORKDIR", "N"),
                    help=argparse.SUPPRESS)   # internal: gate child mode
    ap.add_argument("--standby-child", nargs=3,
                    metavar=("WORKDIR", "N", "PORT"),
                    help=argparse.SUPPRESS)   # internal: gate child mode
    return ap


def main() -> None:
    args = build_parser().parse_args()

    if args.recover_child:
        _recover_child(args.recover_child[0], int(args.recover_child[1]))
        return
    if args.standby_child:
        _standby_child(args.standby_child[0], int(args.standby_child[1]),
                       int(args.standby_child[2]))
        return
    if args.standby_gate:
        gate = run_standby_gate(smoke=args.smoke)
        print(f"# standby gate: {gate['admitted']} admitted, lag at kill "
              f"{gate['lag_at_kill']}, {gate['replayed']} replayed by the "
              f"promoted standby, {gate['lost']} lost, "
              f"{gate['mismatched']} mismatched; rolling restart: "
              f"{gate['rolled']} in flight across {gate['restarts']} "
              f"worker restarts, {gate['roll_lost']} lost, "
              f"{gate['roll_mismatched']} mismatched, reload epochs "
              f"{gate['reload_epochs']}")
        if (gate["lost"] or gate["mismatched"] or gate["roll_lost"]
                or gate["roll_mismatched"] or gate["problems"]):
            for p in gate["problems"]:
                print(f"# FAIL: {p}", file=sys.stderr)
            if (gate["lost"] or gate["mismatched"] or gate["roll_lost"]
                    or gate["roll_mismatched"]):
                print("# FAIL: zero-downtime path lost or corrupted "
                      "admitted requests", file=sys.stderr)
            sys.exit(1)
        print("# zero-downtime: promotion and rolling restart lost zero "
              "admitted requests; config epochs converged")
        return
    if args.recover_gate:
        gate = run_recover_gate(smoke=args.smoke)
        print(f"# recover gate: {gate['admitted']} admitted, "
              f"{gate['replayed']} replayed "
              f"({gate['cache_hits']} cache hits), "
              f"{gate['lost']} lost, {gate['mismatched']} mismatched, "
              f"wal pending after: {gate['wal_pending_after']}")
        if gate["lost"] > 0 or gate["mismatched"] > 0:
            print("# FAIL: kill-and-replay lost or corrupted admitted "
                  "requests", file=sys.stderr)
            sys.exit(1)
        print("# admitted-means-durable: SIGKILL lost zero requests")
        return
    if args.telemetry_gate:
        gate = run_telemetry_gate(smoke=args.smoke)
        print(f"# telemetry gate: {gate['requests']} requests, "
              f"{gate['exposition_bytes']} exposition bytes, "
              f"{gate['incomplete_traces']} incomplete trace(s), "
              f"{gate['dropped_spans']} dropped span(s)")
        if gate["problems"]:
            for p in gate["problems"]:
                print(f"# FAIL: {p}", file=sys.stderr)
            sys.exit(1)
        print("# telemetry gate: exposition parses, required series "
              "present, every trace complete, zero dropped spans")
        return
    if args.fleet_gate:
        gate = run_fleet_gate(smoke=args.smoke)
        print(f"# fleet gate: {gate['admitted']} admitted "
              f"({gate['admitted_at_victim']} parked at the victim), "
              f"{gate['replayed']} replayed by {gate['adopter']}, "
              f"{gate['lost']} lost, {gate['mismatched']} mismatched, "
              f"victim wal pending: {gate['victim_wal_pending']}")
        if gate["lost"] or gate["mismatched"] or gate["problems"]:
            for p in gate["problems"]:
                print(f"# FAIL: {p}", file=sys.stderr)
            if gate["lost"] or gate["mismatched"]:
                print("# FAIL: fleet failover lost or corrupted admitted "
                      "requests", file=sys.stderr)
            sys.exit(1)
        print("# fleet failover: SIGKILL lost zero admitted requests; "
              "survivors replayed the victim's WAL and adopted its "
              "tenants")
        return
    if args.speed_gate:
        gate = run_speed_gate(smoke=args.smoke)
        print("mode,wall_s,points,points_per_s,joins,early_retires,"
              "slot_occupancy,batches,recompiles_after_warm,"
              "exec_misses_after_warm")
        for r in (gate["continuous"], gate["batch"]):
            print(f"{r['mode']},{r['wall_s']:.3f},{r['points']},"
                  f"{r['pps']:.0f},{r['joins']},{r['early_retires']},"
                  f"{r['mean_slot_occupancy']:.3f},{r['batches']},"
                  f"{r['recompiles_after_warm']},"
                  f"{r['exec_misses_after_warm']}")
        cont, batch = gate["continuous"], gate["batch"]
        speedup = cont["pps"] / batch["pps"] if batch["pps"] else 0.0
        print(f"# speed gate: continuous {cont['pps']:.0f} pps vs "
              f"batch-at-a-time {batch['pps']:.0f} pps ({speedup:.2f}x), "
              f"{cont['joins']} join(s), {cont['early_retires']} early "
              f"retire(s), short_before_long={cont['short_before_long']}")
        if gate["problems"]:
            for p in gate["problems"]:
                print(f"# FAIL: {p}", file=sys.stderr)
            sys.exit(1)
        print("# continuous batching: device stayed hot — joins filled "
              "freed slots, shorts retired early, zero recompiles after "
              "warm-up")
        return
    if args.energy_gate:
        gate = run_energy_gate(smoke=args.smoke)
        u, c = gate["uncapped"], gate["capped"]
        print(f"# energy gate: {gate['requests']} requests; uncapped "
              f"{u['draw_w']:.3f} W / {u['joules_per_point'] * 1e3:.4f} "
              f"mJ/point in {u['wall_s']:.2f}s; capped at "
              f"{c['cap_watts']:.3f} W -> {c['paced_draw_w']:.3f} W / "
              f"{c['joules_per_point'] * 1e3:.4f} mJ/point in "
              f"{c['wall_s']:.2f}s ({c['throttles']} throttle(s), "
              f"{c['throttled_s']:.2f}s blocked); budget retry_after "
              f"{gate['budget_retry_after']}")
        if gate["problems"]:
            for p in gate["problems"]:
                print(f"# FAIL: {p}", file=sys.stderr)
            sys.exit(1)
        print("# energy gate: modeled draw held under the cap, "
              "joules/point did not regress, budgets reject with an "
              "honest retry_after")
        return
    if args.bucket_sweep:
        rows = run_bucket_sweep(smoke=args.smoke)
        print("workload,policy,requests,trace_waste,trace_buckets,"
              "padding_waste,point_occupancy,recompiles,mean_occupancy,"
              "batches,p99_ms")
        for r in rows:
            print(f"{r['workload']},{r['policy']},{r['requests']},"
                  f"{r['trace_waste']:.3f},{r['trace_buckets']},"
                  f"{r['padding_waste']:.3f},{r['point_occupancy']:.3f},"
                  f"{r['recompiles']},{r['mean_occupancy']:.3f},"
                  f"{r['batches']},{r['p99_ms']:.2f}")
        if not bucket_sweep_gate(rows):
            sys.exit(1)
        print("# bucketing gate: adaptive beats pow2 on zipf padding "
              "waste at equal-or-better compiled-shape count")
        return

    rows = run(fast=not args.full, smoke=args.smoke)
    print("executor,offered_rps,requests,p50_ms,p99_ms,mean_occupancy,"
          "mean_batch_size,batches,modeled_joules,failures")
    for r in rows:
        print(f"{r['executor']},{r['offered_rps']:.0f},{r['requests']},"
              f"{r['p50_latency_s'] * 1e3:.2f},{r['p99_latency_s'] * 1e3:.2f},"
              f"{r['mean_occupancy']:.3f},{r['mean_batch_size']:.2f},"
              f"{r['batches']},{r['modeled_joules']:.3f},{r['failures']}")
    # occupancy should not fall as offered load rises (pressure -> coalesce)
    for executor in {r["executor"] for r in rows}:
        occ = [r["mean_occupancy"] for r in rows if r["executor"] == executor]
        print(f"# {executor}: occupancy trend {['%.2f' % o for o in occ]}")

    ov = run_overlap(smoke=args.smoke)
    lane_desc = ", ".join(
        f"{name}: {st['busy_s']:.3f}s/{st['batches']}b"
        for name, st in sorted(ov["lanes"].items()))
    print(f"# overlap: wall {ov['wall_s']:.3f}s vs lane-busy "
          f"{ov['busy_s']:.3f}s (ratio {ov['overlap_ratio']:.2f}) "
          f"[{lane_desc}]")
    starved = [lane for lane in OVERLAP_LANES if lane not in ov["lanes"]]
    if starved:
        # pool regression: a pinned lane never executed a batch
        print(f"# FAIL: starved lanes {starved}", file=sys.stderr)
        sys.exit(1)
    if ov["overlap_ratio"] > 1.0:
        print("# lanes overlapped: wall < sum of per-lane busy time")
    else:
        print("# warning: no overlap measured (single-core host?)")

    dist = run_distributed(smoke=args.smoke)
    print(f"# distributed lane: {dist['n_points']} points over "
          f"{dist['devices']} device(s), "
          f"{dist['distributed_batches']} batch(es), "
          f"labels_match={dist['labels_match']}")
    if dist["distributed_batches"] < 1:
        # routing regression: the oversized request never reached the
        # distributed paradigm (cost model / budget / bypass broke)
        print("# FAIL: oversized request never landed on the distributed "
              "lane", file=sys.stderr)
        sys.exit(1)
    if not dist["labels_match"]:
        print("# FAIL: sharded labels diverged from the single-device "
              "reference", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
