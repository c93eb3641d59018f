"""The one traffic generator: a traffic file's parameters plus a
configuration's grid become the requests of a run.

A traffic file (``bench/traffic/<mix>.json``) holds only data:

``loop``
    ``"open"``: requests are due on a fixed schedule whatever the service
    does (independent users); ``"closed"``: ``clients`` callers each send
    their next request when the last one has been answered.
``algo``
    ``"kmeans"`` or ``"dbscan"``.
``rate``
    open loop: offered requests per second.
``clients``, ``max_rate``
    closed loop: the number of callers, and a rate the cell never
    reaches, which sizes the pool of distinct requests made in set-up.
``points_per_cluster``, ``features``, ``clusters``, ``min_points``
    optional filters of the configuration's grid.

Every seed gets the same work in another order.  The request shapes are the
filtered grid's tuples in blocks, each block a permutation of all tuples;
the tenants a fixed Zipf-skewed multiset; the open loop's gaps the
quantiles of an exponential distribution (Poisson arrivals at ``rate``),
scaled to fill the window exactly.  The seed shuffles them and draws the
points; the same seed gives the same requests.

The points follow the paper's generator (arXiv:2112.04800 §II.C): Gaussian
clusters with centres uniform in ±``center_range`` and per-cluster,
per-feature standard deviations uniform in [``min_sigma``,
``max_sigma``], equal cluster sizes, shuffled; float32.  Each request's
points are drawn from ``(seed, request index)``, so no two requests are
alike and the service's result cache never answers for the device.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class Shape:
    features: int
    clusters: int
    points_per_cluster: int

    @property
    def n(self) -> int:
        return self.clusters * self.points_per_cluster


@dataclass
class Request:
    index: int
    algo: str
    shape: Shape
    tenant: str
    params: Dict
    due: Optional[float] = None      # seconds after the window opens
    data: Optional[np.ndarray] = None


def shapes(config: dict, traffic: dict) -> List[Shape]:
    """The configuration's grid, filtered by the traffic file."""
    grid = config["grid"]
    out = []
    for d, c, s in itertools.product(grid["features"], grid["clusters"],
                                     grid["points_per_cluster"]):
        sh = Shape(int(d), int(c), int(s))
        if ("features" in traffic and d not in traffic["features"]
                or "clusters" in traffic and c not in traffic["clusters"]
                or "points_per_cluster" in traffic
                and s not in traffic["points_per_cluster"]
                or sh.n < int(traffic.get("min_points", 0))):
            continue
        out.append(sh)
    if not out:
        raise ValueError("the traffic file's filters leave no grid tuple")
    return out


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *stream])


def _tenant_multiset(count: int, tenants: int, zipf_s: float) -> List[int]:
    """``count`` tenant indices, tenant i weighted 1/(i+1)**s, by largest
    remainders (the same multiset for every seed)."""
    w = np.array([1.0 / (i + 1) ** zipf_s for i in range(tenants)])
    quota = w / w.sum() * count
    base = np.floor(quota).astype(int)
    rest = np.argsort(-(quota - base), kind="stable")[: count - base.sum()]
    base[rest] += 1
    return [i for i, c in enumerate(base) for _ in range(c)]


def params_for(algo: str, shape: Shape, config: dict,
               rng: np.random.Generator) -> Dict:
    if algo == "kmeans":
        km = config["kmeans"]
        return {"k": shape.clusters, "tol": float(km["tol"]),
                "max_iters": int(km["max_iters"]), "init": km["init"],
                "seed": int(rng.integers(0, 2**31 - 1))}
    if algo == "dbscan":
        db = config["dbscan"]
        return {"eps": float(db["eps_per_sqrt_feature"]
                             * math.sqrt(shape.features)),
                "min_pts": int(db["min_pts_per_feature"] * shape.features)}
    raise ValueError(f"unknown algo {algo!r}")


def plan(config: dict, traffic: dict, seed: int, seconds: float,
         count: Optional[int] = None) -> List[Request]:
    """The requests of one run, in the order they are sent.  ``count``
    overrides how many (the open loop's is rate × seconds; the closed
    loop's is max_rate × seconds)."""
    algo = traffic["algo"]
    tuples = shapes(config, traffic)
    if count is None:
        rate = traffic["rate"] if traffic["loop"] == "open" \
            else traffic["max_rate"]
        count = max(1, int(round(float(rate) * seconds)))
    rng = seeded_rng(seed, 0)
    order: List[Shape] = []
    while len(order) < count:
        order.extend(tuples[i] for i in rng.permutation(len(tuples)))
    order = order[:count]
    tenants = _tenant_multiset(count, int(config["tenants"]),
                               float(config["tenant_zipf_s"]))
    tenants = [tenants[i] for i in rng.permutation(count)]
    dues: List[Optional[float]] = [None] * count
    if traffic["loop"] == "open":
        q = -np.log1p(-(np.arange(count) + 0.5) / count)
        gaps = rng.permutation(q) * (seconds / q.sum())
        dues = list(np.concatenate(([0.0], np.cumsum(gaps)[:-1])))
    reqs = []
    for i in range(count):
        prng = seeded_rng(seed, 1, i)
        reqs.append(Request(index=i, algo=algo, shape=order[i],
                            tenant=f"tenant-{tenants[i]}",
                            params=params_for(algo, order[i], config, prng),
                            due=None if dues[i] is None else float(dues[i])))
    return reqs


def points(config: dict, seed: int, index: int, shape: Shape) -> np.ndarray:
    """One request's points: (n, features) float32."""
    g = config["generator"]
    rng = seeded_rng(seed, 2, index)
    c, d, s = shape.clusters, shape.features, shape.points_per_cluster
    r = float(g["center_range"])
    centers = rng.uniform(-r, r, (c, d)).astype(np.float32)
    sigmas = rng.uniform(float(g["min_sigma"]), float(g["max_sigma"]),
                         (c, d)).astype(np.float32)
    labels = np.repeat(np.arange(c), s)
    noise = rng.standard_normal((c * s, d), dtype=np.float32)
    x = centers[labels] + noise * sigmas[labels]
    return x[rng.permutation(c * s)]


def make_data(config: dict, seed: int, reqs: List[Request]) -> None:
    for r in reqs:
        r.data = points(config, seed, r.index, r.shape)
