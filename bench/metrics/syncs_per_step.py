"""Blocking device-to-host reads per step program: Σ ``syncs`` over Σ
``steps``, the counters of the window's ``steps`` spans (one per device-lane
item, or per quantum of a continuous K-Means slot; service/trace.py)."""

import spans


def read(ctx):
    steps = syncs = 0
    for sp in spans.complete(ctx.spans):
        if sp["name"] == "steps":
            steps += int(sp["attrs"].get("steps", 0))
            syncs += int(sp["attrs"].get("syncs", 0))
    if not steps:
        return None
    return syncs / steps
