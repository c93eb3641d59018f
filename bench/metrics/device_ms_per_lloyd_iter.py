"""Device time of the K-Means step programs, ms per Lloyd iteration.

Device time: the summed duration, in the trace's ``XLA Modules`` line, of
the programs whose name matches :data:`PROGRAMS` (the jitted masked Lloyd
steps: ``masked_kmeans_step_jit`` of the XLA lane and
``fused_masked_kmeans_step_jit`` of the Pallas lane).  Iterations: the sum
of ``iterations`` that the window's K-Means answers from a device lane
report.
"""

import re

PROGRAMS = re.compile(r"masked_kmeans_step")
DEVICE_LANES = ("pallas-kernel", "jax-ref", "distributed")


def read(ctx):
    if ctx.summary is None:
        return None
    iters = sum(r.result["iterations"] for r in ctx.answered("kmeans")
                if r.result.get("executor") in DEVICE_LANES)
    ns = ctx.summary.module_ns(PROGRAMS)
    if not iters or not ns:
        return None
    return ns / 1e6 / iters
