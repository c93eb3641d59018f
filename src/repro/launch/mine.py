"""The paper's data mining app, as a launcher: DBSCAN/K-Means jobs with
cancellation, persistence and progress readout.

    PYTHONPATH=src python -m repro.launch.mine --algo dbscan \
        --features 2 --clusters 6 --size 1024 --workdir /tmp/mine
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.core import dbscan, kmeans
from repro.core.cancellation import CancellationToken
from repro.core.jobs import JobState, JobStore
from repro.data.synthetic import ClusterSpec, make_blobs
from repro.runtime import backend as backend_mod
from repro.runtime.preemption import HoldAlive, PreemptionGuard


def run_mining_job(
    *,
    algo: str,
    features: int,
    clusters: int,
    size: int,
    workdir: str,
    use_kernel: bool = True,
    seed: int = 0,
    token: CancellationToken | None = None,
) -> dict:
    backend_mod.load()
    jobs = JobStore(os.path.join(workdir, "jobs.db"))
    jobs.recover_orphans()
    jid = jobs.enqueue("mine", {
        "algo": algo, "features": features, "clusters": clusters,
        "size": size,
    })
    job = jobs.claim_next(kind="mine")
    assert job is not None

    spec = ClusterSpec(features, clusters, size)
    key = jax.random.PRNGKey(seed)
    x, _, _ = make_blobs(key, spec)
    token = token or CancellationToken()

    t0 = time.time()
    result: dict = {"job_id": job.job_id, "algo": algo}
    with PreemptionGuard(token), HoldAlive(jobs, job.job_id):
        if algo == "dbscan":
            cfg = dbscan.DBSCANConfig.paper_defaults(features)
            cfg = dbscan.DBSCANConfig(
                eps=cfg.eps, min_pts=cfg.min_pts, use_kernel=use_kernel
            )
            res = dbscan.fit_cancellable(
                x, cfg, token=token,
                on_progress=lambda cid, nexp: jobs.report_progress(
                    job.job_id, clusters_found=cid, expansions=nexp
                ),
            )
            result.update(
                n_clusters=int(res.n_clusters),
                noise=int(np.sum(np.asarray(res.labels) == 0)),
                cancelled=res.cancelled,
            )
        elif algo == "kmeans":
            cfg = kmeans.KMeansConfig(k=clusters, use_kernel=use_kernel)
            res = kmeans.fit_cancellable(
                key, x, cfg, token=token,
                on_progress=lambda it, shift: jobs.report_progress(
                    job.job_id, step=it, shift=shift
                ),
            )
            result.update(
                iterations=int(res.iterations),
                inertia=float(res.inertia),
                converged=bool(res.converged),
                cancelled=res.cancelled,
            )
        else:
            raise ValueError(f"unknown algo {algo!r}")

        final = JobState.SUSPENDED if result.get("cancelled") \
            else JobState.SUCCEEDED
        jobs.transition(job.job_id, final)
    result["wall_s"] = time.time() - t0
    result["final_state"] = final.value
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", choices=("dbscan", "kmeans"), required=True)
    ap.add_argument("--features", type=int, default=2)
    ap.add_argument("--clusters", type=int, default=6)
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--workdir", default="/tmp/repro_mine")
    ap.add_argument("--no-kernel", action="store_true")
    args = ap.parse_args()
    backend_mod.enable_compile_cache()
    out = run_mining_job(
        algo=args.algo, features=args.features, clusters=args.clusters,
        size=args.size, workdir=args.workdir, use_kernel=not args.no_kernel,
    )
    print(out)


if __name__ == "__main__":
    main()
