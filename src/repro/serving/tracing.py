"""Host spans of the serving round loop, on the profiler's clock.

``SpanLog`` keeps the last ``CAPACITY`` spans of the round loop
(``ContinuousScheduler``) and its executor (``ContinuousLLMExecutor``)
in a ring.  It is always on: a span costs a few microseconds of host
time, a decode round of the served model tens of milliseconds.

Each span is stamped with ``time.time_ns()``, the clock ``jax.profiler``
puts host events on (TSL's ``GetCurrentTimeNanos``), so the spans lie on
the same axis as the device operations of a profile taken over the same
rounds.  Each also enters a ``jax.profiler.TraceAnnotation`` of its own
name, so an operator's profile shows the same spans.  ``anchor`` pairs
one ``perf_counter_ns`` reading with one ``time_ns`` reading, for
readers that cut the spans to a window measured with ``perf_counter``.

The spans the program writes (DESIGN.md §10 round loop):

  ``sched.start``   a round's start: admission, masks, the latency draw
                    (ids: ``round``, ``admitted`` gids);
  ``sched.latency`` the ``LatencyModel`` draw inside it;
  ``sched.round``   a round's end: its executor calls and bookkeeping
                    (ids: ``round``);
  ``exec.<kind>``   one executor call, ``kind`` prefill or decode (ids:
                    ``call``), with four children ``exec.<kind>.prepare``
                    (host inputs to device arrays), ``.dispatch`` (the
                    jitted program until it returns), ``.fetch`` (the
                    sampled ids to the host, which waits for the device)
                    and ``.report`` (the locator's report to the host).
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16            # spans kept: a 51 s window at ~10 a round


class Span(NamedTuple):
    seq: int                  # running index, from 0 at the log's start
    name: str
    start_ns: int             # time.time_ns(), the profiler's host clock
    end_ns: int
    parent: int               # seq of the enclosing span, -1 at the top
    ids: Dict[str, object]    # round / call indices, admitted gids


class _Open:
    """One span while it is open (a context manager)."""

    __slots__ = ("log", "name", "ids", "seq", "parent", "start_ns",
                 "annotation")

    def __init__(self, log: "SpanLog", name: str, ids: dict):
        self.log, self.name, self.ids = log, name, ids

    def __enter__(self) -> dict:
        log = self.log
        self.seq = log._seq
        log._seq += 1
        self.parent = log._open[-1] if log._open else -1
        log._open.append(self.seq)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start_ns = time.time_ns()
        return self.ids

    def __exit__(self, *exc) -> bool:
        end_ns = time.time_ns()
        self.annotation.__exit__(*exc)
        log = self.log
        log._open.pop()
        log.spans.append(Span(self.seq, self.name, self.start_ns, end_ns,
                              self.parent, self.ids))
        return False


class SpanLog:
    """A bounded ring of host spans.  ``span(name, **ids)`` opens one;
    the dict it yields takes ids known only inside the span."""

    def __init__(self):
        self.spans: collections.deque = collections.deque(maxlen=CAPACITY)
        self.anchor: Tuple[int, int] = (time.perf_counter_ns(),
                                        time.time_ns())
        self._open: List[int] = []
        self._seq = 0

    def span(self, name: str, **ids) -> _Open:
        return _Open(self, name, ids)

    def to_time_ns(self, perf_counter_s: float) -> int:
        """A ``time.perf_counter()`` reading on the spans' clock."""
        pc_ns, t_ns = self.anchor
        return t_ns + round(perf_counter_s * 1e9) - pc_ns
