"""Chip smoke test: the served clustering path on a TPU, checked end to end.

Drives the clustering service the way ``repro.launch.serve_mine`` builds
it (a ``ClusteringService`` behind a ``MiningClient``) at the paper's
largest grid tuple: 4 features, 8 clusters of 2,048 points (n = 16,384 per
request).  Eight requests from two tenants -- four K-Means (k = 8,
tol = 1e-6) and four DBSCAN (eps = 2, min_pts = 40) -- run on the
``pallas-kernel`` lane, then again on the ``jax-ref`` lane, whose
distances are the direct-difference forms of ``kernels/*/ref.py``, and
the two are compared.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # the distributed lane, 2x2 host

``--chips 4`` runs only the ``distributed`` lane: one DBSCAN request at
n = 65,536 (8,192 points per cluster), routed there by the real HBM
budget, and one K-Means request on the same data, routed there by a small
``device_budget_bytes``; each is compared with the same request on one
chip through ``pallas-kernel``.

Wall times are host-clock seconds around whole requests, not device
metrics.  The last line of standard output is a JSON object with
``"ok": true`` and the device as JAX reports it; the script prints it only
when every check passed, and exits nonzero otherwise -- also when JAX
finds no TPU, or when the repository's ``src/`` is not beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

FEATURES = 4
CLUSTERS = 8
POINTS_PER_CLUSTER = 2048          # the paper's largest grid tuple
POINTS_PER_CLUSTER_4CHIP = 8192    # 4 n^2 bytes ~ 17 GB: over one chip
KMEANS_TOL = 1e-6
KMEANS_MAX_ITERS = 300
MIN_AGREEMENT = 0.999              # share of points with the same label
CENTROID_ATOL = 1e-3
RESULT_TIMEOUT_S = 900.0
# small enough that the K-Means request's working set is over budget
KMEANS_BUDGET_BYTES = 1 << 20


class SmokeFailure(RuntimeError):
    """A check failed; the message says which."""


def _import_repro():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SmokeFailure(f"no repository source tree at {SRC}")
    sys.path.insert(0, SRC)
    import repro

    if not all(os.path.abspath(p).startswith(SRC) for p in repro.__path__):
        raise SmokeFailure(f"repro imported from {list(repro.__path__)}, "
                           f"not from {SRC}")


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _make_data(seed: int, index: int, points_per_cluster: int):
    import jax
    import numpy as np

    from repro.data.synthetic import ClusterSpec, make_blobs

    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    x, _, _ = make_blobs(key, ClusterSpec(FEATURES, CLUSTERS,
                                          points_per_cluster))
    return np.asarray(x, np.float32)


def _kmeans_params(seed: int):
    return {"k": CLUSTERS, "tol": KMEANS_TOL,
            "max_iters": KMEANS_MAX_ITERS, "seed": seed}


def _dbscan_params():
    from repro.core.dbscan import DBSCANConfig

    cfg = DBSCANConfig.paper_defaults(FEATURES)
    return {"eps": cfg.eps, "min_pts": cfg.min_pts}


def _serve(requests, executor, **service_kwargs):
    """Submit every request at once through a fresh service; return
    ``[(result, wall_s, plan_summary)]`` in request order."""
    from repro.service import ClusteringService, MiningClient

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        service = ClusteringService(workdir, **service_kwargs)
        client = MiningClient(service=service)
        out = []
        with service:
            t0 = time.perf_counter()
            handles = [client.submit(tenant, algo, x, params=params,
                                     executor=executor)
                       for tenant, algo, x, params in requests]
            for (tenant, algo, _, _), h in zip(requests, handles):
                try:
                    result = h.result(RESULT_TIMEOUT_S)
                except Exception as e:
                    raise SmokeFailure(
                        f"{algo} request of {tenant} on "
                        f"{executor or 'auto'} failed: {e!r}") from e
                job = service.executor.jobs.get(h.job_id)
                out.append((result, time.perf_counter() - t0,
                            job.params["plan"]))
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _label_agreement(algo: str, got, ref) -> float:
    """Share of points with the same label.  DBSCAN clusters are matched
    first: each cluster of ``got`` maps to the reference cluster it
    overlaps most (noise stays noise)."""
    import numpy as np

    got = np.asarray(got, np.int64)
    ref = np.asarray(ref, np.int64)
    if algo == "dbscan":
        table = np.zeros((got.max() + 1, ref.max() + 1), np.int64)
        np.add.at(table, (got, ref), 1)
        mapping = table.argmax(axis=1)
        mapping[0] = 0
        got = mapping[got]
    return float(np.mean(got == ref))


def _centroids(x, labels, k: int):
    """Mean of each cluster's points: the centroids of a converged run."""
    import numpy as np

    labels = np.asarray(labels, np.int64)
    out = np.zeros((k, x.shape[1]), np.float64)
    for j in range(k):
        if np.any(labels == j):
            out[j] = x[labels == j].mean(axis=0)
    return out


def _compare(requests, got, ref, got_lane: str, ref_lane: str):
    """Check one lane's results against another's; return the report."""
    import numpy as np

    rows = []
    for (tenant, algo, x, params), (g, g_s, _), (r, r_s, _) in zip(
            requests, got, ref):
        _check(g["executor"] == got_lane,
               f"{algo} result came from {g['executor']}, not {got_lane}")
        _check(r["executor"] == ref_lane,
               f"{algo} reference came from {r['executor']}, "
               f"not {ref_lane}")
        agree = _label_agreement(algo, g["labels"], r["labels"])
        row = {"algo": algo, "tenant": tenant, "n": int(x.shape[0]),
               "agreement": agree,
               "mismatched_points": int(round((1 - agree) * x.shape[0])),
               f"{got_lane}_wall_s": g_s, f"{ref_lane}_wall_s": r_s}
        if algo == "kmeans":
            row.update(iterations=g["iterations"],
                       ref_iterations=r["iterations"])
            _check(g["converged"] and r["converged"],
                   f"K-Means did not converge in {params['max_iters']} "
                   f"iterations ({got_lane}: {g['iterations']}, "
                   f"{ref_lane}: {r['iterations']})")
            shift = float(np.max(np.abs(
                _centroids(x, g["labels"], params["k"])
                - _centroids(x, r["labels"], params["k"]))))
            row["centroid_max_abs_diff"] = shift
            _check(shift <= CENTROID_ATOL,
                   f"K-Means centroids differ by {shift} > {CENTROID_ATOL}")
        else:
            row.update(expansions=g["expansions"],
                       ref_expansions=r["expansions"],
                       clusters=g["n_clusters"],
                       ref_clusters=r["n_clusters"])
            _check(g["n_clusters"] == r["n_clusters"],
                   f"DBSCAN found {g['n_clusters']} clusters on "
                   f"{got_lane}, {r['n_clusters']} on {ref_lane}")
        _check(agree >= MIN_AGREEMENT,
               f"{algo} labels agree on {agree:.6f} of points "
               f"(< {MIN_AGREEMENT})")
        rows.append(row)
    return rows


def _timed_compile(fn, *args, **kwargs):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    return compiled, time.perf_counter() - t0


def _compile_kernels(n: int):
    """AOT-compile the served K-Means step and the DBSCAN kernels for the
    chip; return ``{name: wall_s}`` after checking each holds a Mosaic
    kernel (no interpret mode)."""
    import jax
    import jax.numpy as jnp

    from repro.core import dbscan
    from repro.service.dispatch import _dbscan_config, _kmeans_config
    from repro.service.exec_cache import default_exec_cache

    cache = default_exec_cache()
    kcfg = _kmeans_config(_kmeans_params(0), use_kernel=True)
    t0 = time.perf_counter()
    cache.warm_kmeans(n, FEATURES, kcfg)   # the program the service runs
    seconds = {"kmeans_step": time.perf_counter() - t0}
    texts = {"kmeans_step": cache.kmeans_step(n, FEATURES, kcfg).as_text()}
    dcfg = _dbscan_config(_dbscan_params(), use_kernel=True)
    x = jax.ShapeDtypeStruct((n, FEATURES), jnp.float32)
    f = jax.ShapeDtypeStruct((n,), jnp.bool_)
    i = jax.ShapeDtypeStruct((), jnp.int32)
    carry = (jax.ShapeDtypeStruct((n,), jnp.int32), f, f, f, i, i)
    degree, seconds["dbscan_degree"] = _timed_compile(
        dbscan._degree_step, x, cfg=dcfg)
    # the expansion kernel inside the device loop the host loop dispatches
    expand, seconds["dbscan_expand"] = _timed_compile(
        dbscan._expand_steps, x, f, carry, i, cfg=dcfg)
    texts["dbscan_degree"] = degree.as_text()
    texts["dbscan_expand"] = expand.as_text()
    for name, text in texts.items():
        _check("tpu_custom_call" in text,
               f"compiled {name} holds no tpu_custom_call: the Pallas "
               f"kernel did not compile to Mosaic")
    return seconds


def _report(title: str, rows) -> None:
    print(f"# {title}")
    for row in rows:
        print("  " + json.dumps(row, sort_keys=True))


def run_one_chip(seed: int) -> None:
    import jax

    n = CLUSTERS * POINTS_PER_CLUSTER
    compile_s = _compile_kernels(n)
    print(f"# compile wall seconds (AOT, before serving): "
          f"{json.dumps(compile_s, sort_keys=True)}")
    dparams = _dbscan_params()
    requests = []
    for i in range(8):
        algo = "kmeans" if i < 4 else "dbscan"
        params = _kmeans_params(seed * 100 + i) if algo == "kmeans" \
            else dict(dparams)
        requests.append((f"tenant-{i % 2}", algo,
                         _make_data(seed, i, POINTS_PER_CLUSTER), params))
    got = _serve(requests, "pallas-kernel")
    ref = _serve(requests, "jax-ref")
    rows = _compare(requests, got, ref, "pallas-kernel", "jax-ref")
    _report("per request (wall seconds are host-clock, submit to result, "
            "all 8 submitted at once)", rows)
    print(f"# mismatched points per request: "
          f"{[r['mismatched_points'] for r in rows]}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"# peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def run_four_chips(seed: int) -> None:
    import jax

    devices = jax.devices()
    _check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees "
                              f"{len(devices)}")
    x = _make_data(seed, 0, POINTS_PER_CLUSTER_4CHIP)
    dreq = [("tenant-0", "dbscan", x, _dbscan_params())]
    kreq = [("tenant-1", "kmeans", x, _kmeans_params(seed))]
    # routed by the cost model, not pinned: DBSCAN by the chip's real HBM
    # budget, K-Means by a budget its working set exceeds
    dist = (_serve(dreq, None)
            + _serve(kreq, None, device_budget_bytes=KMEANS_BUDGET_BYTES))
    shard_bytes = x.nbytes // len(devices)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    print(f"# peak_bytes_in_use per device after the distributed lane: "
          f"{peaks}")
    _check(all(p >= shard_bytes for p in peaks),
           f"sharded arrays did not reach every device: peaks {peaks}, "
           f"one shard is {shard_bytes} bytes")
    for (_, algo, _, _), (_, _, plan) in zip(dreq + kreq, dist):
        print(f"# {algo} plan: {json.dumps(plan, sort_keys=True)}")
        _check(plan["paradigm"] == "distributed" and plan["shards"] == 4,
               f"{algo} ran on {plan['paradigm']} with "
               f"{plan['shards']} shard(s), not distributed over 4")
    one = _serve(dreq + kreq, "pallas-kernel")
    rows = _compare(dreq + kreq, dist, one, "distributed", "pallas-kernel")
    _report("distributed (4 chips) vs pallas-kernel (1 chip); wall seconds "
            "are host-clock", rows)
    print(f"# mismatched points per request: "
          f"{[r['mismatched_points'] for r in rows]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and K-Means inits")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed lane on a 2x2 host")
    args = ap.parse_args(argv)
    try:
        _import_repro()
        from repro.runtime import backend

        cache_dir = backend.enable_compile_cache()
        import jax

        dev = jax.devices()[0]
        _check(dev.platform == "tpu",
               f"JAX found no TPU (platform {dev.platform!r})")
        backend.discover_backend()     # an unrecorded device_kind raises
        print(f"# device_kind {dev.device_kind!r}, {len(jax.devices())} "
              f"device(s), compile cache {cache_dir}")
        t0 = time.perf_counter()
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
        print(f"# total wall seconds: {time.perf_counter() - t0:.3f}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
