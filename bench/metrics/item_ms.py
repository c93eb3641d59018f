"""Time an item's own work takes, ms: the mean, over the window's answered
requests that entered a lane, of their ``steps`` and ``host_compute``
spans summed per request (every quantum and attempt; service/trace.py
spans, host clock).  Nothing where the program records no such span."""

import spans


def read(ctx):
    turns = spans.lane_turns(ctx)
    if not turns:
        return None
    return 1e3 * sum(own for _, own in turns) / len(turns)
