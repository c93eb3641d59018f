"""Share of its roofline that the DBSCAN neighbour passes reach, %.

Least time: for each DBSCAN answer from a device lane, what the algorithm
needs at the request's real n and d (bench/roofline.py
``neighbor_request``, against bench/peaks.py): one degree pass of n×n
distances, which compute bounds, and per expansion only the frontier read
and the reach written.  Recomputing every distance at every expansion, as
the kernels do today, therefore reads as a low share.  Kernel time: the
device time of the programs whose name matches :data:`PROGRAMS` in the
trace's ``XLA Modules`` line: the jitted degree and expansion steps of one
chip (``_degree_step``, ``_expand_step``) and the ring programs of the
distributed lane (``shard_map`` of ``make_ring_degree`` /
``make_ring_expand``), summed over the cell's chips: on p chips the least
time is a p-th of one chip's, and the kernels use p chips for their time.
"""

import re

import roofline

PROGRAMS = re.compile(r"_degree_step|_expand_step|shard_map|ring")
DEVICE_LANES = ("pallas-kernel", "jax-ref", "distributed")


def read(ctx):
    if ctx.summary is None:
        return None
    least = 0.0
    for r in ctx.answered("dbscan"):
        if r.result.get("executor") not in DEVICE_LANES:
            continue
        s = r.req.shape
        work = roofline.neighbor_request(s.n, s.features,
                                         r.result["expansions"])
        t, _ = roofline.least_seconds(work, ctx.peaks)
        least += t
    ns = ctx.summary.module_ns(PROGRAMS)
    if not least or not ns:
        return None
    return 100.0 * least / (ns / 1e9)
