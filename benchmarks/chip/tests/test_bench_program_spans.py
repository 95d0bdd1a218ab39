"""The readers of the program's own spans and counters, checked on a
small synthetic run worked out by hand: two decode rounds of host spans,
the harness's call spans and device operations of a trace (CPU)."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import program_spans  # noqa: E402
import spec  # noqa: E402
import trace_reduce as tr  # noqa: E402
from repro.serving.tracing import Span, SpanLog  # noqa: E402

OFFSET = 500                  # the profile's start on the spans' clock, ns


def _spans():
    """Two rounds on the spans' clock (ns); a third starts and is cut by
    the window's end; an attempt that started no round has no ids."""
    rows = [  # seq, name, start, end, parent, ids
        (1, "sched.latency", 1100, 1200, 0, {}),
        (0, "sched.start", 1000, 1300, -1, {"round": 0, "admitted": ()}),
        (4, "exec.decode.prepare", 1600, 1900, 3, {}),
        (5, "exec.decode.dispatch", 1900, 2100, 3, {}),
        (6, "exec.decode.fetch", 2100, 4600, 3, {}),
        (7, "exec.decode.report", 4600, 4700, 3, {}),
        (3, "exec.decode", 1500, 4800, 2, {"call": 0}),
        (2, "sched.round", 1400, 5000, -1, {"round": 0}),
        (8, "sched.start", 5100, 5200, -1, {"round": 1, "admitted": ()}),
        (11, "exec.decode.prepare", 5500, 5600, 10, {}),
        (12, "exec.decode.dispatch", 5600, 6200, 10, {}),
        (13, "exec.decode.fetch", 6200, 8700, 10, {}),
        (14, "exec.decode.report", 8700, 8750, 10, {}),
        (10, "exec.decode", 5400, 8800, 9, {"call": 1}),
        (9, "sched.round", 5300, 9000, -1, {"round": 1}),
        (15, "sched.start", 9100, 9400, -1, {"round": 2, "admitted": ()}),
        (16, "sched.start", 9500, 9600, -1, {}),
    ]
    log = SpanLog()
    log.anchor = (0, 0)           # perf_counter seconds * 1e9 == time_ns
    log.spans.extend(Span(*r) for r in rows)
    return log


def _trace():
    """Device busy [950,1200) [1500,3900) [5200,8000) on the trace's clock
    (the spans' less OFFSET); the harness's call spans around each
    ``exec.decode``."""
    ops = [tr.Op("%fusion.1 = f32[8] fusion(...)", 950, 1200, 0),
           tr.Op("%fusion.2 = f32[8] fusion(...)", 1500, 3900, 0),
           tr.Op("%fusion.3 = f32[8] fusion(...)", 5200, 8000, 0)]
    spans = [tr.Span("bench.decode", 1450 - OFFSET, 4850 - OFFSET),
             tr.Span("bench.decode", 5350 - OFFSET, 8850 - OFFSET)]
    return tr.Trace(ops=ops, spans=spans)


def _ctx(log=None, trace=None, decoded=30):
    calls = [types.SimpleNamespace(kind="decode", t0=1450e-9, t1=4850e-9),
             types.SimpleNamespace(kind="decode", t0=5350e-9, t1=8850e-9)]
    metrics = types.SimpleNamespace(decoded_rows=decoded)
    executor = types.SimpleNamespace(
        pool_groups=4, coding=types.SimpleNamespace(num_workers=5))
    if log is not None:
        executor.spans = log
    sched = types.SimpleNamespace(executor=executor, metrics=metrics)
    return types.SimpleNamespace(
        clock={"sched": sched}, t_start=0.0, t_end=10e-6, calls=calls,
        trace=trace, tracer=types.SimpleNamespace(t_on=0.0, t_off=10e-6))


def read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_round_loop_self_time():
    # round 0: start 300 - latency 100, round 3600 - decode 3300 -> 500;
    # round 1: 100 + 3700 - 3400 -> 400; round 2 never ran
    assert read("sched.self_ms", _ctx(_spans())) == pytest.approx(450e-6)


def test_decode_enqueue_time():
    # prepare + dispatch: 300 + 200 and 100 + 600
    assert read("exec.decode_enqueue_ms", _ctx(_spans())) == \
        pytest.approx(600e-6)


def test_live_row_share():
    # 30 live rows of 2 decode calls x 4 groups x 5 coded streams
    assert read("exec.live_row_share", _ctx(_spans())) == 75.0
    ctx = _ctx(_spans())
    ctx.calls = [types.SimpleNamespace(kind="prefill", t0=0.0, t1=1e-9)]
    assert read("exec.live_row_share", ctx) is None


def test_trace_offset_is_fitted_from_the_call_spans():
    ctx = _ctx(_spans(), _trace())
    assert program_spans.trace_offset_ns(ctx, ctx.clock["sched"].executor
                                         .spans) == OFFSET


def test_idle_time_by_innermost_span():
    """Idle [1200,1500) [3900,5200) [8000,8350) of the window [950,8350),
    cut where the host's innermost span changes."""
    idle = program_spans.idle_by_span(_ctx(_spans(), _trace()))
    assert idle == {"exec.decode.prepare": 200 + 100,
                    "exec.decode.dispatch": 100 + 100,
                    "exec.decode.fetch": 200 + 200,
                    "exec.decode.report": 100 + 50,
                    "exec.decode": 100 + 100 + 50,
                    "sched.round": 200 + 100 + 50,
                    "sched.start": 100,
                    None: 100 + 100,
                    "window": 7400}


def test_idle_while_enqueueing():
    assert read("device.idle_enqueue", _ctx(_spans(), _trace())) == \
        pytest.approx(100.0 * 500 / 7400)


def test_innermost_pieces_cover_the_window():
    pieces = program_spans.innermost(list(_spans().spans), 0, 10_000)
    assert pieces[0] == (0, 1000, None) and pieces[-1] == (9600, 10_000,
                                                           None)
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    assert (1100, 1200, "sched.latency") in pieces
    assert (4700, 4800, "exec.decode") in pieces


@pytest.mark.parametrize("name", ["sched.self_ms", "exec.decode_enqueue_ms",
                                  "device.idle_enqueue",
                                  "exec.live_row_share"])
def test_silent_on_a_program_without_spans_or_counters(name):
    """A program without the span log or the counters (the parent of the
    change that added them) reads None, and never raises."""
    ctx = _ctx(None, _trace())
    ctx.clock["sched"].metrics = types.SimpleNamespace()
    assert read(name, ctx) is None


def test_device_reader_silent_without_a_trace():
    assert read("device.idle_enqueue", _ctx(_spans(), None)) is None
