"""The program's own host spans and counters, for per-layer metrics.

``ContinuousLLMExecutor.spans`` (``repro.serving.tracing.SpanLog``) holds
the round loop's and the executor's spans, stamped with ``time.time_ns``,
the clock the profiler stamps host events with; ``ContinuousScheduler
.metrics`` holds the counters.  A reader reaches both through the
scheduler in the clock record (``ctx.clock["sched"]``).  A program
without a span log gives ``None`` here, and its readers stay silent.

A trace counts its times from the profile's start, so a span goes onto
the trace's axis less that start, which ``trace_offset_ns`` fits from
the harness's own ``bench.*`` spans in the trace against the same calls'
``perf_counter`` starts.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

import numpy as np

CALL_SPANS = ("bench.prefill", "bench.decode")


def log(ctx):
    """The program's span log, or None where it keeps none."""
    sched = (ctx.clock or {}).get("sched")
    return getattr(getattr(sched, "executor", None), "spans", None)


def between(spans_log, t0_s: float, t1_s: float) -> list:
    """Spans that lie wholly inside [t0_s, t1_s] (``perf_counter``
    seconds), in the order they ended."""
    lo, hi = spans_log.to_time_ns(t0_s), spans_log.to_time_ns(t1_s)
    return [s for s in spans_log.spans if lo <= s.start_ns and s.end_ns <= hi]


def self_ns(spans: list) -> Dict[int, int]:
    """Each span's nanoseconds less those of its direct children."""
    own = {s.seq: s.end_ns - s.start_ns for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= s.end_ns - s.start_ns
    return own


def innermost(spans: list, lo: float, hi: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """[lo, hi) cut into (start, end, name) pieces, ``name`` the
    innermost span the host was in there, None where it was in none."""
    seqs = {s.seq for s in spans}
    children = collections.defaultdict(list)
    for s in spans:
        children[s.parent if s.parent in seqs else None].append(s)
    out: list = []

    def walk(a, b, name, key):
        t = a
        for k in sorted(children[key], key=lambda s: s.start_ns):
            if k.start_ns > t:
                out.append((t, k.start_ns, name))
            walk(k.start_ns, k.end_ns, k.name, k.seq)
            t = max(t, k.end_ns)
        if b > t:
            out.append((t, b, name))

    walk(lo, hi, None, None)
    return [(max(a, lo), min(b, hi), n) for a, b, n in out
            if min(b, hi) > max(a, lo)]


def overlap_by_name(gaps: List[Tuple[float, float]], pieces: list
                    ) -> Dict[Optional[str], float]:
    """Nanoseconds of ``gaps`` (sorted, disjoint) under each name of
    ``pieces`` (sorted, disjoint, from ``innermost``)."""
    out: Dict[Optional[str], float] = collections.defaultdict(float)
    i = 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            pa, pb, name = pieces[j]
            out[name] += min(b, pb) - max(a, pa)
            j += 1
    return dict(out)


def trace_offset_ns(ctx, spans_log) -> Optional[int]:
    """The profile's start on the spans' clock: the median, over the
    traced calls, of a call's start on the spans' clock less its
    ``bench.<kind>`` span's start in the trace."""
    bench = sorted(s.start for s in ctx.trace.spans if s.name in CALL_SPANS)
    on, off = ctx.tracer.t_on, ctx.tracer.t_off
    base = spans_log.anchor[1]            # keeps the float64s small
    starts = np.asarray(sorted(spans_log.to_time_ns(c.t0) - base
                               for c in ctx.calls
                               if on <= c.t0 and c.t1 <= off), np.float64)
    if not bench or not starts.size:
        return None
    guess = starts[0] - bench[0]          # the first traced call in both
    fit = np.median([starts[np.argmin(np.abs(starts - guess - b))] - b
                     for b in bench])
    return base + round(float(fit))


def idle_by_span(ctx) -> Optional[Dict[Optional[str], float]]:
    """Idle device nanoseconds of the traced window (averaged over the
    chips) under the innermost program span the host was in, and the
    window's nanoseconds under ``"window"``."""
    import trace_reduce
    spans_log = log(ctx)
    if ctx.trace is None or spans_log is None:
        return None
    offset = trace_offset_ns(ctx, spans_log)
    if offset is None:
        return None
    lo, hi = ctx.trace.window()
    spans = [s._replace(start_ns=s.start_ns - offset,
                        end_ns=s.end_ns - offset)
             for s in between(spans_log, ctx.tracer.t_on, ctx.tracer.t_off)]
    pieces = innermost(spans, lo, hi)
    total: Dict[Optional[str], float] = collections.defaultdict(float)
    devices = ctx.trace.devices
    for d in devices:
        gaps = trace_reduce.idle_gaps(ctx.trace, d, lo, hi)
        for name, ns in overlap_by_name(gaps, pieces).items():
            total[name] += ns / len(devices)
    total["window"] = hi - lo
    return dict(total)
