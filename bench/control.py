"""Readings that set the correctness limits: the program's numbers over many
seeds, and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--seconds S]

In one process, the cell's service is built and warmed once (as
``run.py`` does).  For each seed of ``--seeds`` the cell's traffic runs for
``--seconds`` at the cell's own load, and the sampled answers are compared
with the float32 reference exactly as a benchmark run compares them: these
are the lower readings.  For each seed of ``--control-seeds`` the control
-- the reference computed in bfloat16, the nearest precision below the
configuration's float32, put in the program's place -- is compared with
the float32 reference on the same sampled requests: these are the upper
readings.  One JSON line per seed and kind; the limits in the
configuration file lie between the largest lower and the smallest upper
reading.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile

import compare
import drive
import loadgen
import run
import warm as warm_mod


class _Answer:
    """A request with the control's answer in the program's place."""

    def __init__(self, rec, result):
        self.req = rec.req
        self.result = result


def control_answer(rec) -> dict:
    import reference

    req = rec.req
    x = req.data
    if req.algo == "kmeans":
        p = req.params
        init = reference.sample_init(p["seed"], x.shape[0], p["k"])
        got = reference.kmeans(x, init, tol=p["tol"],
                               max_iters=p["max_iters"], precision="bf16")
        return {"labels": got["labels"], "inertia": got["inertia"]}
    got = reference.dbscan(x, eps=req.params["eps"],
                           min_pts=req.params["min_pts"], precision="bf16")
    return {"labels": got["labels"], "expansions": got["expansions"],
            "executor": "control"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    config = run.load_json(run.BENCH, "configs", f"{cell['config']}.json")
    traffic = run.load_json(run.BENCH, "traffic", f"{cell['traffic']}.json")
    seconds = args.seconds or float(bench["run_seconds"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    jax = run.import_program()
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    from repro.service import (BacklogFull, ClusteringService,
                               EnergyBudgetExceeded, MiningClient,
                               RateLimited)

    shed_types = (BacklogFull, RateLimited, EnergyBudgetExceeded)
    workdir = tempfile.mkdtemp(prefix="bench-control-")
    service = ClusteringService(workdir)
    client = MiningClient(service=service)
    windows = {}
    try:
        service.start()
        warm_mod.warm(service, config, traffic, seeds[0])
        for seed in seeds:
            reqs = loadgen.plan(config, traffic, seed, seconds)
            loadgen.make_data(config, seed, reqs)
            if traffic["loop"] == "open":
                win = drive.open_loop(client, reqs, seconds, shed_types)
            else:
                win = drive.closed_loop(client, reqs, seconds,
                                        int(traffic["clients"]), shed_types)
            windows[seed] = win
    finally:
        service.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    for seed in seeds:
        win = windows[seed]
        sample = run.check_sample(win, traffic, seed)
        rows = [run.reference_numbers(r) for r in sample]
        out = {"seed": seed, "kind": "program", "sampled": len(sample),
               "answered": sum(r.result is not None for r in win.records),
               "numbers": compare.worst(rows),
               "per_request": rows}
        print(json.dumps(out), flush=True)
        if seed in control:
            rows = [run.reference_numbers(_Answer(r, control_answer(r)))
                    for r in sample]
            print(json.dumps({"seed": seed, "kind": "control",
                              "sampled": len(sample),
                              "numbers": compare.worst(rows),
                              "per_request": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
