"""Batcher (``serving/batcher.py``): real requests per admitted group,
as a share of K, over the groups the window admitted.  Moves
``itl_ms_p95``: fuller groups mean fewer admission rounds, each of
which recomputes the whole pool and stalls every request's gap."""

import numpy as np


def read(ctx):
    fills = [run.valid.sum() / run.k for run in ctx.runs.values()]
    return 100.0 * float(np.mean(fills)) if fills else None
