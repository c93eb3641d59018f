"""Device idle time put down to checkpoints, % of the traced window: the
idle time of the first device that ``bench/spans.py`` credits to
``checkpoint`` spans (the read-back of an item's state and its write)."""

import spans


def read(ctx):
    return spans.idle_pct(ctx, "checkpoint")
