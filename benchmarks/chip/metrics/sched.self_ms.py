"""Round loop (``serving/continuous.py``): median host milliseconds per
round of the scheduler's own work, from the program's spans: its
``sched.start`` and ``sched.round`` spans less their children (the
``sched.latency`` draw, the executor's ``exec.*`` calls).  What a wrapper
of the executor does outside the ``exec.*`` spans stays in the reading:
here the harness's ``TimedExecutor``: its ``bench.<kind>`` annotation and its call
record (copies of the ids and masks).  Moves ``itl_ms_p95``."""

import numpy as np

import program_spans


def read(ctx):
    spans_log = program_spans.log(ctx)
    if spans_log is None:
        return None
    spans = program_spans.between(spans_log, ctx.t_start, ctx.t_end)
    own = program_spans.self_ns(spans)
    parts = {"sched.start": {}, "sched.round": {}}
    for s in spans:
        if s.name in parts and "round" in s.ids:
            parts[s.name][s.ids["round"]] = own[s.seq]
    start, end = parts["sched.start"], parts["sched.round"]
    per_round = [start[r] + end[r] for r in start.keys() & end.keys()]
    return float(np.median(per_round)) / 1e6 if per_round else None
