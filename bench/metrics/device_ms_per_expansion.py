"""Device time of the DBSCAN requests, ms per kernel pass: the device's
busy time in the traced window (mean over the cell's chips) over the sum,
across the window's DBSCAN answers from a device lane, of their
``expansions`` plus one (the degree pass).  In the DBSCAN cells every
device operation belongs to a DBSCAN request."""

DEVICE_LANES = ("pallas-kernel", "jax-ref", "distributed")


def read(ctx):
    if ctx.summary is None:
        return None
    passes = sum(r.result["expansions"] + 1 for r in ctx.answered("dbscan")
                 if r.result.get("executor") in DEVICE_LANES)
    if not passes:
        return None
    return ctx.summary.busy_s * 1e3 / passes
