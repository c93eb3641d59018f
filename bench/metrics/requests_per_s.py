"""Requests answered inside the window, over the window's length (host
clock).  Every request of the window counts; none is left out."""


def read(ctx):
    w = ctx.window
    done = sum(1 for r in w.records
               if r.result is not None and r.done is not None
               and r.done <= w.t1)
    return done / (w.t1 - w.t0)
