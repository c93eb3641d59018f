"""Operations and bytes that one kernel call needs, from logical shapes.

Every count uses the request's real sizes: n points, d features, k
centroids.  Never the padded shapes the program runs at (points padded to a
bucket, features padded to 128 lanes, centroids padded to 8 rows): padding,
extra precision passes and recomputation therefore read as a lower share of
the roofline, and removing them can never read above 100%.  Where an
algorithm can skip work (DBSCAN's expansions need not recompute the
distances of the degree pass), only what every implementation needs is
counted.

The cross term of a distance matrix between n and m points counts as
2·n·m·d FLOPs.  Bytes are the inputs read once plus the outputs written
once, in float32 (4 bytes) with int32 labels and counts.
"""

from __future__ import annotations

from dataclasses import dataclass

F32 = 4
I32 = 4


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, times: float) -> "Work":
        return Work(self.flops * times, self.bytes * times)


ZERO = Work(0.0, 0.0)


def kmeans_step(n: int, k: int, d: int) -> Work:
    """One Lloyd step: distances of n points to k centroids, the argmin, and
    the new centroids.

    FLOPs: the cross term (2·n·k·d) plus the per-centroid sums of the
    assigned points (n·d).  Bytes: points and centroids read, assignment
    and new centroids written.
    """
    flops = 2.0 * n * k * d + n * d
    nbytes = F32 * n * d + F32 * k * d + I32 * n + F32 * k * d
    return Work(flops, nbytes)


def neighbor_degree(n: int, d: int) -> Work:
    """The DBSCAN degree pass: for each of n points, the count of points
    within eps.  Cross term 2·n·n·d; points read once, counts written.
    Every implementation of the algorithm needs it once per request."""
    return Work(2.0 * n * n * d, F32 * n * d + I32 * n)


def neighbor_expand(n: int, d: int) -> Work:
    """What one DBSCAN frontier expansion needs beyond the degree pass: the
    frontier read and the reach written, one byte a point.

    No FLOPs: each core point joins a frontier once, so the neighbour
    queries of all expansions of a request add up to at most one more n×n
    pass, and an implementation that keeps the degree pass's adjacency
    needs none at all.  Counting only what every implementation needs
    keeps the share at or under 100% whether the expansions recompute every
    distance (as the kernels do today), query only the frontier's rows, or
    read a stored adjacency."""
    return Work(0.0, 2.0 * n)


def neighbor_request(n: int, d: int, expansions: int) -> Work:
    """One DBSCAN request: the degree pass once and ``expansions``
    frontier expansions."""
    return neighbor_degree(n, d) + neighbor_expand(n, d).scaled(expansions)


def least_seconds(work: Work, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = work.flops / peaks["bf16_flops"]
    t_memory = work.bytes / peaks["hbm_bandwidth"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
