"""Round loop (``serving/continuous.py``): median host milliseconds
between one executor call's end and the next call's start, less the
harness's pacing waits in between.  Moves ``itl_ms_p95``."""

import numpy as np

import window


def read(ctx):
    gaps = window.host_gaps_ms(ctx.calls, ctx.clock["pacing"])
    return float(np.median(gaps)) if gaps else None
