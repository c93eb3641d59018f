"""Warm-up: build every program the cell's traffic can reach, in set-up.

A request of n points runs padded to the bucket its service's policy gives
it.  The default adaptive policy starts at the next power of two and later
re-fits its edges to observed sizes, never above that power of two, so a
request of n points is bucketed at n or at the power of two above it; in a
batch, or joining one in flight, it runs at the batch's padded size.  Which
lane runs it depends on the batch it lands in (up to ``max_batch``
requests).  For every shape of the traffic, every such padded size and
every lane the service's own cost model can choose for it, one item runs
through that lane's paradigm here, so that the window compiles nothing:
the step programs, the Pallas kernels and the small eager programs around
them (initial centroids, masks, the DBSCAN frontier bookkeeping).
"""

from __future__ import annotations

import time
from typing import Dict, List, Set, Tuple

import numpy as np

import loadgen


def padded_sizes(policy, n: int) -> Set[int]:
    return {max(n, int(policy.bucket(n))), int(policy.bucket_ceiling(n)), n}


def _noop(*_args, **_kwargs) -> None:
    return None


def padded(x, n_pad: int, algo: str, eps: float):
    """The request's points at ``n_pad`` rows: zeros for K-Means, and for
    DBSCAN the dispatch module's far-diagonal rows, which lie more than eps
    from every point and from each other, as a batch pads them."""
    from repro.service.dispatch import far_diagonal_pad

    n = x.shape[0]
    out = np.zeros((n_pad, x.shape[1]), np.float32)
    out[:n] = x
    if algo == "dbscan" and n < n_pad:
        far_diagonal_pad(out, n, eps, float(x.max()))
    return out


def plan_items(service, algo: str, shapes, params_of) -> List[Tuple]:
    """(shape, padded size, lane) triples one request can run as.

    Requests of one compatibility class (same features and parameters) share
    batches, and a batch runs every item at the batch's padded size, so a
    request of n points can run at the padded size of any request of its
    class that is at least n.  The lane is any the cost model picks for
    that padded size at a batch of 1 to ``max_batch`` requests."""
    max_batch = int(service.batcher.max_batch)
    classes: Dict[Tuple, List] = {}
    for shape in shapes:
        p = {k: v for k, v in params_of(shape).items() if k != "seed"}
        key = (shape.features, tuple(sorted(p.items())))
        classes.setdefault(key, []).append(shape)
    out = []
    for members in classes.values():
        pads = set()
        for shape in members:
            pads |= padded_sizes(service.bucket_policy, shape.n)
        for shape in members:
            params = params_of(shape)
            for n_pad in sorted(p for p in pads if p >= shape.n):
                lanes = set()
                for batch in range(1, max_batch + 1):
                    lanes.update(service.registry.candidates(
                        algo, n_pad, shape.features, batch, params,
                        bucket=lambda n: n))
                out.extend((shape, n_pad, lane) for lane in sorted(lanes))
    return out


def warm(service, config: dict, traffic: dict, seed: int) -> Dict[str, float]:
    """Run one item per (shape, padded size, lane) of :func:`plan_items`;
    returns counts and seconds for the set-up report."""
    from repro.service.dispatch import ItemView

    algo = traffic["algo"]
    t0 = time.perf_counter()
    shapes = loadgen.shapes(config, traffic)
    params = {}
    data = {}
    for j, shape in enumerate(shapes):
        p = loadgen.params_for(algo, shape, config,
                               loadgen.seeded_rng(seed, 3, j))
        if algo == "kmeans":
            p["max_iters"] = 2          # not part of the compiled program
        params[shape] = p
        data[shape] = loadgen.points(config, seed, 1_000_000 + j, shape)
    slowest = 0.0
    runs = plan_items(service, algo, shapes, params.__getitem__)
    for shape, n_pad, lane in runs:
        t_item = time.perf_counter()
        p, x = params[shape], data[shape]
        paradigm = service.registry.get(lane)
        plan = paradigm.plan(algo, p, batch_size=1, n_max=n_pad,
                             features=shape.features)
        item = ItemView(index=0, x_pad=padded(
            x, n_pad, algo, float(p.get("eps", 1.0))),
            length=shape.n, seed=int(p.get("seed", 0)))
        outcome = paradigm.execute(plan, [item], None, _noop, _noop)
        if outcome.suspended:
            raise RuntimeError(f"warm-up of {lane} at {n_pad} points was "
                               f"suspended")
        slowest = max(slowest, time.perf_counter() - t_item)
    return {"warm_runs": len(runs), "warm_s": time.perf_counter() - t0,
            "slowest_item_s": slowest}
