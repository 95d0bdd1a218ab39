"""Drive the program's serving loop on the host clock.

The harness reaches the program only through its public surface:

  * ``TimedExecutor`` subclasses ``ContinuousLLMExecutor``: each
    ``prefill`` / ``decode`` call is stamped on the host clock once the
    sampled ids are on the host, wrapped in a profiler span
    (``bench.prefill`` / ``bench.decode``), and recorded;
  * ``HostClock`` is the ``LatencyModel`` handed to the scheduler.

``ContinuousScheduler`` admits and retires on its event clock, which a
``LatencyModel`` advances.  ``HostClock`` binds that clock to the host
clock.  It still makes the seeded draw of the base model, so the draw
still decides which workers straggle and are masked, but it rescales the
draw so the round fires when the host clock says the round's calls will
have ended: the host time at the round's start plus the median of the
last few measured calls of each kind the round makes (a prefill when it
admits, a decode when groups are in the pool).  Simulated network delay
so adds no host time.  A round that admits a request not yet due on the
host clock (the event clock ran ahead) first waits until it is due (a
``bench.pacing`` span), so no request is served before it is due; no
other round ever waits.

The window ends at the first round boundary after ``seconds``: there
``HostClock`` raises ``WindowClosed`` out of ``ContinuousScheduler.run``.
"""

from __future__ import annotations

import collections
import time
from typing import List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving import ContinuousLLMExecutor, LatencyModel

from window import Call


class WindowClosed(Exception):
    """Raised at the first round boundary after the window's end."""


RECENT = 5                        # calls per kind behind a round estimate


class TimedExecutor(ContinuousLLMExecutor):
    """The program's executor, with each call timed and recorded."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.calls: List[Call] = []
        self.state = None             # the pool state the last call returned
        self.forget_durations()

    def forget_durations(self) -> None:
        self.recent_s = {k: collections.deque(maxlen=RECENT)
                         for k in ("prefill", "decode")}

    def expected_s(self, kind: str) -> float:
        """Median host seconds of the last few calls of ``kind``."""
        d = self.recent_s[kind]
        return float(np.median(d)) if d else 0.0

    def _timed(self, kind, fn, state, x, group_mask, mask, attack,
               live_mask, locate_quorum):
        t0 = time.perf_counter()
        with TraceAnnotation(f"bench.{kind}"):
            tokens, state, report = fn(state, x, group_mask, mask, attack,
                                       live_mask=live_mask,
                                       locate_quorum=locate_quorum)
        t1 = time.perf_counter()
        self.calls.append(Call(
            kind=kind, t0=t0, t1=t1, tokens=np.array(tokens, np.int32),
            group_mask=np.array(group_mask, np.float32),
            mask=np.array(mask, np.float32),
            attack=(None if attack is None or not attack.active
                    else np.array(attack.mask, np.float32))))
        self.recent_s[kind].append(t1 - t0)
        self.state = state
        return tokens, state, report

    def prefill(self, state, prompts, admit_mask, mask, attack=None,
                live_mask=None, locate_quorum=None):
        return self._timed("prefill", super().prefill, state, prompts,
                           admit_mask, mask, attack, live_mask,
                           locate_quorum)

    def decode(self, state, tokens, active_mask, mask, attack=None,
               live_mask=None, locate_quorum=None):
        return self._timed("decode", super().decode, state, tokens,
                           active_mask, mask, attack, live_mask,
                           locate_quorum)


class Tracer:
    """Profiler trace of a sub-window, started and stopped at round
    boundaries so that every traced round is whole."""

    def __init__(self, directory: str, start_s: float, stop_s: float):
        self.directory = directory
        self.start_s, self.stop_s = start_s, stop_s
        self.t_on = self.t_off = None
        self.cost_s = 0.0             # host time spent starting/stopping

    def at_boundary(self, host_s: float) -> None:
        if self.t_on is None and host_s >= self.start_s:
            t = time.perf_counter()
            jax.profiler.start_trace(self.directory)
            self.t_on = time.perf_counter()
            self.cost_s += self.t_on - t
        elif (self.t_on is not None and self.t_off is None
              and host_s >= self.stop_s):
            self.stop()

    def stop(self) -> None:
        if self.t_on is not None and self.t_off is None:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.cost_s += time.perf_counter() - self.t_off


class HostClock(LatencyModel):
    """A ``LatencyModel`` whose rounds take the time they take on the
    host.  ``attach`` it to the scheduler and executor before ``run``."""

    def __init__(self, base: LatencyModel, wait_for: int):
        super().__init__()
        object.__setattr__(self, "_base", base)
        object.__setattr__(self, "_wait_for", wait_for)
        object.__setattr__(self, "_state", None)

    def attach(self, sched, executor, t_start: float, window_s: float,
               tracer: Optional[Tracer] = None):
        object.__setattr__(self, "_state", {
            "sched": sched, "executor": executor, "t0": t_start,
            "window_s": window_s, "tracer": tracer, "seen": 0,
            "live": set(),
            "fire_ms": 0.0, "lag_ms": [], "pacing": [],
            "boundaries": []})

    @property
    def record(self) -> dict:
        return self._state

    def _event_now_ms(self, st) -> tuple:
        """(event-clock now, how many groups this round admits, whether
        it decodes).  Admission entries of the public trace carry the
        time they were made at; a round that admits nothing follows the
        last one at its fire time.  Admit and free entries keep the set
        of groups in the pool."""
        trace = st["sched"].trace
        admits = []
        for e in trace[st["seen"]:]:
            if e[0] == "admit":
                admits.append(e)
            elif e[0] == "free":
                st["live"].discard(e[1])
        st["seen"] = len(trace)
        decodes = bool(st["live"])
        st["live"].update(e[1] for e in admits)
        if admits:
            return float(admits[-1][3]), len(admits), decodes
        return st["fire_ms"], 0, decodes

    def sample(self, rng, n):
        times = np.asarray(self._base.sample(rng, n), np.float64)
        st = self._state
        if st is None:                  # unattached: the base model
            return times
        now_ms, admits, decodes = self._event_now_ms(st)
        host_s = time.perf_counter() - st["t0"]
        if host_s >= st["window_s"] or now_ms >= st["window_s"] * 1e3:
            raise WindowClosed()
        if st["tracer"] is not None:
            st["tracer"].at_boundary(host_s)
            host_s = time.perf_counter() - st["t0"]
        st["lag_ms"].append(host_s * 1e3 - now_ms)
        due_ms = max((r.arrival_ms for g in st["sched"].groups[-admits:]
                      for r in g.plan.requests), default=0.0) \
            if admits else 0.0
        if due_ms > host_s * 1e3:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.pacing"):
                time.sleep(due_ms / 1e3 - host_s)
            st["pacing"].append((t0, time.perf_counter()))
        st["boundaries"].append(time.perf_counter())
        ex = st["executor"]
        est_s = ((ex.expected_s("prefill") if admits else 0.0)
                 + (ex.expected_s("decode") if decodes else 0.0))
        host_ms = (time.perf_counter() - st["t0"]) * 1e3
        trigger = max(host_ms + 1e3 * est_s - now_ms, 1e-6)
        kth = np.sort(times)[self._wait_for - 1]
        st["fire_ms"] = now_ms + trigger
        # a positive rescale keeps the order, so the same workers straggle
        return times * (trigger / kth)
