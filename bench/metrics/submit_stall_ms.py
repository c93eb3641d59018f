"""The longest a ``MiningClient.submit`` call held the open-loop sender
past the next request's due time, ms: the largest lateness of a send
against its schedule (bench/drive.py).  Admission runs on the caller's
thread (precheck, the WAL append, enqueue), so a stall there delays every
request due behind it; a closed loop, whose callers wait anyway, records
no lateness and reads nothing."""


def read(ctx):
    late = ctx.window.lateness
    if not late:
        return None
    return 1e3 * max(late)
