"""Device: share of the traced window in which no operation ran on the
chip (averaged over the chips used).  Moves ``tokens_per_s``."""

import numpy as np

import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    busy = np.mean([trace_reduce.busy_ns(ctx.trace, d, lo, hi)
                    for d in ctx.trace.devices])
    return 100.0 * (1.0 - busy / (hi - lo))
