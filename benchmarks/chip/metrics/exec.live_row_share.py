"""Model step, decode: share of the coded rows the decode calls computed
that served a real request not yet retired, from the program's counter
``decoded_rows`` (``ServingMetrics``) over the window's decode calls
times the ``pool_groups x (N+1)`` coded streams every call computes.  A
group holds its slot until its longest request retires.  Moves
``tokens_per_s``."""


def read(ctx):
    sched = ctx.clock.get("sched")
    decoded = getattr(getattr(sched, "metrics", None), "decoded_rows", None)
    calls = sum(c.kind == "decode" for c in ctx.calls)
    if decoded is None or not calls:
        return None
    executor = sched.executor
    computed = calls * executor.pool_groups * executor.coding.num_workers
    return 100.0 * decoded / computed
