"""Executor, pool prefill, in open-loop chat: share of the window the
host spent in prefill calls.  Moves ``itl_ms_p95``: the share of gaps
that hold an admission round, which recomputes the whole pool, sets
where the p95 gap falls."""


def read(ctx):
    d = sum(c.t1 - c.t0 for c in ctx.calls if c.kind == "prefill")
    return 100.0 * d / ctx.window_s
