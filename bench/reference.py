"""Plain float32 K-Means and DBSCAN, independent of the program under test.

These are the yardstick that decides ``correct``.  They import nothing from
``src/`` and take nothing the program made: only the request's points and
parameters.

K-Means (paper §II.C): Lloyd's algorithm from k distinct points drawn at
random (the paper's "initial cluster centers were selected randomly"), in
single precision, stopping when the sum of absolute centroid displacements
falls under ``tol`` or after ``max_iters`` iterations.  A centroid with no
points keeps its place.  The labels and the inertia (the sum of each
point's squared distance to its centroid) returned are those of the
assignment computed in the last iteration; the centroids are those after
its update.

DBSCAN (paper §II.C): a point is core when at least ``min_pts`` points,
itself included, lie within ``eps``.  Clusters grow breadth-first, level by
level: the seed of each new cluster is the lowest-index core point not yet
in a cluster; every unclaimed point within eps of the current frontier
joins the cluster, and the core points among them form the next frontier.
Label 0 is noise.

``precision="bf16"`` computes the same algorithms on bfloat16 arrays: the
points and centroids are rounded to bfloat16, a distance is taken in the
matrix-unit form ||a||^2 - 2 a.b + ||b||^2, each product is accumulated in
float32 inside the unit, and the result of every array operation (norms,
cross term, distances, centroid sums and means) is rounded to bfloat16.  It
is the control: the nearest precision below the float32 that the
configuration states.

Memory: DBSCAN holds its eps-adjacency as one bit per pair (n²/8 bytes) and
nothing else of size n².  The distances are filled in blocks of rows whose
size follows from n and the worker count, so that the workers' temporaries
together stay under ``WORK_BYTES`` however many CPUs the host reports; every
distance is computed in place, by the same float32 operations in the same
order whatever the block, so the answers do not depend on the block size.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

PRECISIONS = ("float32", "bf16")
# The bound on the DBSCAN workers' temporaries together.  The reference
# runs in the process that holds the service and every request's points,
# after the window; at n = 65,536 the adjacency takes 512 MiB, and 1 GiB
# beside it keeps the whole reference under 2 GiB on any host, while
# leaving blocks of hundreds of rows at the sizes of the one-chip cells.
WORK_BYTES = 1 << 30
# Bytes per pair of a block that a worker holds at once: the float32 sums
# and one float32 temporary (the bfloat16 path: its 2-byte rounding
# scratch; then the 1-byte mask beside the sums).
PAIR_BYTES = 8


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def sample_init(seed: int, n: int, k: int) -> np.ndarray:
    """Indices of the k distinct starting points that a request with this
    K-Means ``seed`` starts from: ``jax.random.choice`` without replacement
    under ``PRNGKey(seed)``, computed on the host's CPU."""
    import jax

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        idx = jax.random.choice(jax.random.PRNGKey(seed), n, (k,),
                                replace=False)
        return np.asarray(idx)


def _sq_dists_f32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(len(a), len(b)) squared distances by direct differences, float32,
    feature by feature; holds the result and one temporary of its size."""
    out = np.zeros((a.shape[0], b.shape[0]), np.float32)
    diff = np.empty_like(out)
    for j in range(a.shape[1]):
        np.subtract(a[:, j, None], b[None, :, j], out=diff)
        np.multiply(diff, diff, out=diff)
        out += diff
    return out


def _sq_dists_bf16(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The same on bfloat16 arrays, in the matrix-unit form.  A product of
    two bfloat16 values is exact in float32; the cross term sums them in
    float32 in feature order, which is the order of a matrix product of
    more than one row, so the result does not depend on the block's shape
    (a one-row product may sum in another order)."""
    import ml_dtypes

    a = _bf16(a)
    b = _bf16(b)
    na = _bf16(np.sum(a * a, axis=1, dtype=np.float32))
    nb = _bf16(np.sum(b * b, axis=1, dtype=np.float32))
    out = np.zeros((a.shape[0], b.shape[0]), np.float32)
    prod = np.empty_like(out)
    for j in range(a.shape[1]):
        np.multiply(a[:, j, None], b[None, :, j], out=prod)
        out += prod
    del prod
    scratch = np.empty(out.shape, ml_dtypes.bfloat16)

    def round_out() -> None:
        np.copyto(scratch, out, casting="unsafe")
        np.copyto(out, scratch, casting="unsafe")

    round_out()                                  # cross
    out *= np.float32(2.0)
    round_out()
    np.subtract(na[:, None], out, out=out)
    round_out()
    out += nb[None, :]
    round_out()
    return out


def sq_dists(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return _sq_dists_f32(a, b)
    if precision == "bf16":
        return _sq_dists_bf16(a, b)
    raise ValueError(f"precision must be one of {PRECISIONS}")


def kmeans(x: np.ndarray, init_idx: np.ndarray, *, tol: float,
           max_iters: int, precision: str = "float32") -> dict:
    """Lloyd's algorithm from ``x[init_idx]``; returns labels (int64),
    inertia, centroids (float32), iterations and whether it converged."""
    x = np.asarray(x, np.float32)
    c = x[np.asarray(init_idx)].copy()
    k = c.shape[0]
    xs = _bf16(x) if precision == "bf16" else x
    labels = np.zeros(x.shape[0], np.int64)
    inertia = float("inf")
    it = 0
    converged = False
    while it < max_iters:
        d2 = sq_dists(x, c, precision)
        labels = np.argmin(d2, axis=1)
        inertia = float(np.sum(np.maximum(d2[np.arange(len(x)), labels], 0),
                               dtype=np.float64))
        c_new = c.copy()
        for j in range(k):
            members = labels == j
            if members.any():
                total = np.sum(xs[members], axis=0, dtype=np.float32)
                if precision == "bf16":
                    total = _bf16(total)
                c_new[j] = total / np.float32(members.sum())
        if precision == "bf16":
            c_new = _bf16(c_new)
        shift = float(np.sum(np.abs(c_new - c), dtype=np.float32))
        c = c_new
        it += 1
        if shift < tol:
            converged = True
            break
    return {"labels": labels, "inertia": inertia, "centroids": c,
            "iterations": it,
            "converged": converged}


def _blocks(n: int, size: int):
    return [(s, min(n, s + size)) for s in range(0, n, size)]


def _plan(n: int, threads: int):
    """(workers, rows per block) for filling n columns: together under
    ``WORK_BYTES``."""
    workers = max(1, min(threads or os.cpu_count() or 1,
                         WORK_BYTES // (PAIR_BYTES * n)))
    return workers, max(1, WORK_BYTES // (workers * PAIR_BYTES * n))


def dbscan(x: np.ndarray, *, eps: float, min_pts: int,
           precision: str = "float32", threads: int = 0) -> dict:
    """Labels (int64, 0 = noise, clusters numbered in discovery order) and
    expansions: the number of frontier expansions, summed over clusters
    (each cluster's breadth-first levels, the last one finding no new core
    point).  The eps-adjacency is built once, in blocks of rows, as one bit
    per pair (n²/8 bytes)."""
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    eps2 = np.float32(eps) * np.float32(eps)
    adj = np.zeros((n, (n + 7) // 8), np.uint8)
    deg = np.zeros(n, np.int64)

    def fill(block):
        s, e = block
        near = sq_dists(x[s:e], x, precision) <= eps2
        deg[s:e] = near.sum(axis=1)
        adj[s:e] = np.packbits(near, axis=1)

    workers, rows = _plan(n, threads)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(fill, _blocks(n, rows)))
    # frontier rows are OR-ed in chunks, under the same bound
    chunk = max(1, WORK_BYTES // adj.shape[1])
    core = deg >= min_pts
    labels = np.zeros(n, np.int64)
    cid = 0
    expansions = 0
    while True:
        todo = np.flatnonzero(core & (labels == 0))
        if todo.size == 0:
            break
        cid += 1
        frontier = todo[:1]
        while frontier.size:
            near = np.zeros(adj.shape[1], np.uint8)
            for s in range(0, frontier.size, chunk):
                near |= np.bitwise_or.reduce(adj[frontier[s:s + chunk]],
                                             axis=0)
            reached = np.unpackbits(near, count=n).astype(bool)
            new = reached & (labels == 0)
            labels[new] = cid
            frontier = np.flatnonzero(new & core)
            expansions += 1
    return {"labels": labels, "expansions": expansions}
