"""Model step, decode: median host milliseconds per decode call from the
call's start until the jitted step returns, from the program's spans
``exec.decode.prepare`` (inputs to device arrays) and
``exec.decode.dispatch``.  Moves ``itl_ms_p95``."""

import collections

import numpy as np

import program_spans

PHASES = ("exec.decode.prepare", "exec.decode.dispatch")


def read(ctx):
    spans_log = program_spans.log(ctx)
    if spans_log is None:
        return None
    per_call = collections.defaultdict(int)
    for s in program_spans.between(spans_log, ctx.t_start, ctx.t_end):
        if s.name in PHASES:
            per_call[s.parent] += s.end_ns - s.start_ns
    return (float(np.median(list(per_call.values()))) / 1e6
            if per_call else None)
