"""Find the knee of an open-loop cell: the highest offered rate the service
sustains.

    python3 bench/sweep.py --config <config> --traffic <mix> --rates 10,20,40 \
        [--seconds 8] [--seed 1]

``--config`` and ``--traffic`` name files under ``bench/configs`` and
``bench/traffic``; the mix must be an open loop.  In one process the
service is built and warmed once (as ``run.py`` does), then each rate is
offered for ``--seconds`` with that traffic mix,
one window after the other.  For each rate a line reports the offered and
answered rates, the client-timed latency median and 95th percentile, how
late the generator ran, and how long the answers took to drain after the
window closed.  Below the knee the answered rate follows the offered one
and the drain is short; above it the backlog grows through the window.  A
cell's traffic file then holds about 0.8 times the knee, as a number.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import numpy as np

import run
import drive
import loadgen
import warm as warm_mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    config = run.load_json(run.BENCH, "configs", f"{args.config}.json")
    traffic = run.load_json(run.BENCH, "traffic", f"{args.traffic}.json")
    if traffic["loop"] != "open":
        print("sweep: the traffic mix is not an open loop", file=sys.stderr)
        return 2
    jax = run.import_program()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"sweep: JAX found no TPU ({dev.platform})", file=sys.stderr)
        return 2
    from repro.service import (BacklogFull, ClusteringService,
                               EnergyBudgetExceeded, MiningClient,
                               RateLimited)

    shed_types = (BacklogFull, RateLimited, EnergyBudgetExceeded)
    workdir = tempfile.mkdtemp(prefix="bench-sweep-")
    service = ClusteringService(workdir)
    client = MiningClient(service=service)
    rows = []
    try:
        service.start()
        w = warm_mod.warm(service, config, traffic, args.seed)
        print(f"# warm-up: {w['warm_runs']} items in {w['warm_s']:.3f} s",
              flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(traffic, rate=rate)
            reqs = loadgen.plan(config, mix, args.seed + 1 + i, args.seconds)
            loadgen.make_data(config, args.seed + 1 + i, reqs)
            win = drive.open_loop(client, reqs, args.seconds, shed_types)
            done = [r for r in win.records if r.result is not None]
            lat = np.asarray([r.latency for r in done]) * 1e3
            in_window = sum(r.done <= win.t1 for r in done)
            last = max((r.done for r in done), default=win.t1)
            late = np.asarray(win.lateness) * 1e3
            row = {
                "offered_per_s": rate,
                "answered_per_s": in_window / args.seconds,
                "sent": len(win.records),
                "shed": sum(r.shed is not None for r in win.records),
                "errors": sum(r.error is not None for r in win.records),
                "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
                "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
                "drain_s": max(0.0, last - win.t1),
                "lateness_p50_ms": float(np.percentile(late, 50)),
                "lateness_max_ms": float(late.max()),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(1.0)
    finally:
        service.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
