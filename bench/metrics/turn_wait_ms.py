"""Time a request spent in its lane while other items ran, ms: the mean,
over the window's answered requests that entered a lane, of (its
``deliver`` start − its entry into the lane, i.e. its ``execute`` start or
its ``join`` mark) − its own ``steps`` and ``host_compute`` time
(service/trace.py spans, host clock)."""

import spans


def read(ctx):
    turns = spans.lane_turns(ctx)
    if not turns:
        return None
    return 1e3 * sum(lane - own for lane, own in turns) / len(turns)
