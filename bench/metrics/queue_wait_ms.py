"""Waiting time per request between admission and a lane, ms: the mean
over the window's requests of their ``queue_wait``, ``batch_wait`` and
``lane_wait`` spans (service/trace.py spans, host clock), summed per
request."""

STAGES = ("queue_wait", "batch_wait", "lane_wait")


def read(ctx):
    return ctx.mean_stage_ms(STAGES)
