"""Share of its roofline that the K-Means step reaches, %.

Least time: for each K-Means answer from a device lane, its iterations
times the least time of one Lloyd step at the request's real n, k and d
(bench/roofline.py ``kmeans_step``, against bench/peaks.py: the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth; at these shapes the
bytes bound it).  Kernel time: the device time of the step programs, as in
device_ms_per_lloyd_iter.
"""

import re

import roofline

PROGRAMS = re.compile(r"masked_kmeans_step")
DEVICE_LANES = ("pallas-kernel", "jax-ref", "distributed")


def read(ctx):
    if ctx.summary is None:
        return None
    least = 0.0
    for r in ctx.answered("kmeans"):
        if r.result.get("executor") not in DEVICE_LANES:
            continue
        s = r.req.shape
        t, _ = roofline.least_seconds(
            roofline.kmeans_step(s.n, s.clusters, s.features), ctx.peaks)
        least += t * r.result["iterations"]
    ns = ctx.summary.module_ns(PROGRAMS)
    if not least or not ns:
        return None
    return 100.0 * least / (ns / 1e9)
