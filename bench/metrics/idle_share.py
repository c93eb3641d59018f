"""Share of the traced window in which no operation ran on the device, %:
1 − (union of the device's operation intervals) / (traced window), mean
over the cell's chips (bench/trace_reduce.py)."""


def read(ctx):
    if ctx.summary is None:
        return None
    s = ctx.summary
    return 100.0 * (1.0 - s.busy_s / s.window_s)
