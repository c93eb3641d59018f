"""Device idle time put down to the items' device loops, % of the traced
window: the idle time of the first device that ``bench/spans.py``
credits to ``steps`` spans' self time — a host round trip per iteration
or expansion, host work between step programs."""

import spans


def read(ctx):
    return spans.idle_pct(ctx, "steps")
