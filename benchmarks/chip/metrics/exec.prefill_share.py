"""Executor, pool prefill: share of the window the host spent in
prefill calls.  Moves ``tokens_per_s``."""


def read(ctx):
    d = sum(c.t1 - c.t0 for c in ctx.calls if c.kind == "prefill")
    return 100.0 * d / ctx.window_s
