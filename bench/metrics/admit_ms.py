"""Admission time per request, ms: the mean over the window's requests of
their ``precheck``, ``wal_append`` and ``enqueue`` spans (service/trace.py
spans, host clock), summed per request."""

STAGES = ("precheck", "wal_append", "enqueue")


def read(ctx):
    return ctx.mean_stage_ms(STAGES)
