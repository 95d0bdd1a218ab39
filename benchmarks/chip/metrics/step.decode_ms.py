"""Model step, decode: mean host milliseconds per decode call, from its
start to its sampled ids on the host.  Moves ``itl_ms_p95``."""

import numpy as np


def read(ctx):
    d = [(c.t1 - c.t0) * 1e3 for c in ctx.calls if c.kind == "decode"]
    return float(np.mean(d)) if d else None
