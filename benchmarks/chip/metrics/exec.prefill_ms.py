"""Executor, pool prefill: mean host milliseconds per prefill call,
from its start to its sampled ids on the host.  Moves ``itl_ms_p95``
in short_chat, where the p95 gap is an admission round."""

import numpy as np


def read(ctx):
    d = [(c.t1 - c.t0) * 1e3 for c in ctx.calls if c.kind == "prefill"]
    return float(np.mean(d)) if d else None
