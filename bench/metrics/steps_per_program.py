"""Steps per device program of DBSCAN's host loop: Σ ``steps`` over Σ
``programs``, the counters of the window's ``steps`` spans with ``algo``
``dbscan`` (service/trace.py).  A program that runs one expansion per
dispatch reads 1; one that runs a stretch of expansions on the device
reads more.  Nothing where no span carries ``programs`` (a program
without that counter)."""

import spans


def read(ctx):
    steps = programs = 0
    for sp in spans.complete(ctx.spans):
        attrs = sp["attrs"]
        if (sp["name"] == "steps" and attrs.get("algo") == "dbscan"
                and "programs" in attrs):
            steps += int(attrs.get("steps", 0))
            programs += int(attrs["programs"])
    if not programs:
        return None
    return steps / programs
