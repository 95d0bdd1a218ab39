"""Device, against the program's spans: share of the traced window in
which no operation ran on the chip while the host was preparing or
dispatching an executor call (``exec.*.prepare``, ``exec.*.dispatch``):
the idle time that dispatching ahead would hide.  Moves
``tokens_per_s``."""

import program_spans

ENQUEUE = ("exec.prefill.prepare", "exec.prefill.dispatch",
           "exec.decode.prepare", "exec.decode.dispatch")


def read(ctx):
    idle = program_spans.idle_by_span(ctx)
    if idle is None:
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in ENQUEUE) / idle["window"]
