"""95th percentile of the client-timed latency of the window's answered
requests, ms (see latency_p50_ms).  Reported in cells that answer at least
200 requests a window, so that 10 lie beyond it."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_s()
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
