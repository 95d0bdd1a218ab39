"""Host spans and counters of the continuous round loop
(``serving/tracing.py``): one span per phase of every round and every
executor call, nested as the loop runs them, on the profiler's clock,
and counters that agree with the golden event trace."""

import collections
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import tracing

from test_continuous import K, N_REQUESTS, POOL, _serve, model  # noqa: F401

PHASES = ("prepare", "dispatch", "fetch", "report")


@pytest.fixture(scope="module")
def served(model):  # noqa: F811
    sched, metrics, budgets, _ = _serve(model, seed=0)
    return sched, metrics, list(sched.executor.spans.spans)


def _rounds(sched):
    return [e for e in sched.trace if e[0] == "round"]


def _children(spans):
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s.parent].append(s)
    return kids


def test_one_start_and_one_round_span_per_round(served):
    sched, _, spans = served
    rounds = _rounds(sched)
    starts = [s for s in spans if s.name == "sched.start" and "round" in s.ids]
    ends = [s for s in spans if s.name == "sched.round"]
    assert [s.ids["round"] for s in starts] == [e[1] for e in rounds]
    assert [s.ids["round"] for s in ends] == [e[1] for e in rounds]
    # the admitted gids link each round's start to the golden trace
    assert [s.ids["admitted"] for s in starts] == [e[3] for e in rounds]
    by_seq = {s.seq: s for s in spans}
    for s in spans:
        if s.name == "sched.latency":
            assert by_seq[s.parent].name == "sched.start"
    assert sum(s.name == "sched.latency" for s in spans) == len(rounds)


def test_one_exec_span_per_call_with_its_four_phases(served):
    sched, _, spans = served
    rounds = _rounds(sched)
    by_seq = {s.seq: s for s in spans}
    kids = _children(spans)
    for kind, col in (("prefill", 3), ("decode", 4)):
        calls = [s for s in spans if s.name == f"exec.{kind}"]
        assert len(calls) == sum(bool(e[col]) for e in rounds)
        for c in calls:
            assert by_seq[c.parent].name == "sched.round"
            assert [k.name for k in kids[c.seq]] == [
                f"exec.{kind}.{p}" for p in PHASES]
    calls = [s for s in spans if s.name.count(".") == 1
             and s.name.startswith("exec.")]
    assert [c.ids["call"] for c in sorted(calls, key=lambda s: s.seq)] == \
        list(range(len(calls)))


def test_children_nest_inside_their_parents(served):
    _, _, spans = served
    by_seq = {s.seq: s for s in spans}
    for s in spans:
        assert s.start_ns <= s.end_ns
        if s.parent >= 0:
            p = by_seq[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    kids = _children(spans)
    for siblings in kids.values():
        for a, b in zip(siblings, siblings[1:]):
            assert a.end_ns <= b.start_ns


def test_no_program_span_is_a_harness_span(served):
    _, _, spans = served
    assert spans and not any(s.name.startswith("bench.") for s in spans)


def test_counters_agree_with_the_golden_trace(served):
    sched, metrics, spans = served
    rounds = _rounds(sched)
    decode_calls = sum(bool(e[4]) for e in rounds)
    assert decode_calls == sum(s.name == "exec.decode" for s in spans)
    assert sum(int(g.plan.valid.sum()) for g in sched.groups) == N_REQUESTS
    # a request's first token comes from its prefill, the rest from
    # decode calls that served its row
    assert metrics.decoded_rows == sum(len(r) - 1
                                       for r in sched.results.values())
    assert 0 < metrics.decoded_rows <= decode_calls * POOL * K


def test_spans_leave_the_golden_trace_and_results_unchanged(model):  # noqa: F811
    """A run whose span log records nothing serves the same ids through
    the same event sequence as a run with the log on."""
    on, m_on, _, _ = _serve(model, seed=0)
    real = tracing.SpanLog.span
    tracing.SpanLog.span = lambda self, name, **ids: contextlib.nullcontext(
        ids)
    try:
        off, m_off, _, _ = _serve(model, seed=0)
    finally:
        tracing.SpanLog.span = real
    assert len(off.executor.spans.spans) == 0 < len(on.executor.spans.spans)
    assert on.trace == off.trace
    assert m_on.summary() == m_off.summary()
    assert on.results.keys() == off.results.keys()
    for uid in on.results:
        np.testing.assert_array_equal(on.results[uid], off.results[uid])


def test_ring_keeps_only_its_capacity():
    log = tracing.SpanLog()
    n = tracing.CAPACITY // 2 + 3
    for i in range(n):
        with log.span("exec.decode", call=i):
            with log.span("exec.decode.fetch"):
                pass
    assert len(log.spans) == tracing.CAPACITY
    # a span is kept when it ends: the child before its parent
    last = 2 * n - 1
    assert [s.seq for s in log.spans][-2:] == [last, last - 1]
    assert log.spans[-1].name == "exec.decode"
    assert log.spans[-1].parent == -1 and log.spans[-2].parent == last - 1
    # the oldest spans went first
    assert sorted(s.seq for s in log.spans) == list(
        range(2 * n - tracing.CAPACITY, 2 * n))


def test_a_span_records_when_its_body_raises():
    log = tracing.SpanLog()
    with pytest.raises(KeyError):
        with log.span("sched.start") as ids:
            ids["round"] = 3
            with log.span("sched.latency"):
                raise KeyError("window closed")
    assert [s.name for s in log.spans] == ["sched.latency", "sched.start"]
    assert log.spans[1].ids == {"round": 3}
    with log.span("sched.round"):
        pass
    assert log.spans[-1].parent == -1


def test_spans_lie_on_the_profilers_clock(tmp_path):
    """A span's ``time_ns`` stamps, less the profile's start, are where
    the profiler put its annotation of the same name."""
    from jax.profiler import ProfileData
    log = tracing.SpanLog()
    x = jnp.ones((64,))
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with log.span("exec.decode", call=i):
            x = (x + 1).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    data = ProfileData.from_file(path)
    start = None
    events = []
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        if plane.name.startswith("/host:"):
            events += [e for line in plane.lines for e in line.events
                       if e.name == "exec.decode"]
    assert start is not None and len(events) == 3
    events.sort(key=lambda e: e.start_ns)
    for span, ev in zip(log.spans, events):
        assert abs(span.start_ns - start - ev.start_ns) < 50_000
        assert abs(span.end_ns - start - ev.end_ns) < 50_000
    pc_ns, t_ns = log.anchor
    assert log.to_time_ns(pc_ns / 1e9) == pytest.approx(t_ns, abs=1_000)


def test_programs_and_phases_are_named(served):
    sched, _, _ = served
    ex = sched.executor
    assert ex._prefill.__name__ == "coded_pool_prefill"
    assert ex._decode.__name__ == "coded_pool_decode_step"
    n1 = ex.coding.num_workers

    def shape(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    f32 = jnp.float32
    args = (shape(ex.params), shape(jax.eval_shape(ex.init_state)),
            jax.ShapeDtypeStruct((POOL * K, 1), jnp.int32),
            jax.ShapeDtypeStruct((POOL,), f32),
            jax.ShapeDtypeStruct((n1,), f32), jax.ShapeDtypeStruct((n1,), f32),
            key, jax.ShapeDtypeStruct((), f32), key,
            jax.ShapeDtypeStruct((n1,), f32),
            jax.ShapeDtypeStruct((), jnp.int32))
    text = ex._decode.lower(*args).as_text(debug_info=True)
    assert "jit_coded_pool_decode_step" in text
    for scope in ("encode", "blocks", "unembed", "tail", "sample"):
        assert re.search(rf'jit\(coded_pool_decode_step\)/{scope}["/]',
                         text), scope
