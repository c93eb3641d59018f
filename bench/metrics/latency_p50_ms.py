"""Median client-timed latency of the window's answered requests, ms:
from the request's due time (open loop) or its send (closed loop) to the
answer's arrival (host clock)."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
