"""From a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
operations on each device (the ``XLA Ops`` line of every ``/device:TPU:``
plane) and the harness's own host spans (``bench.*`` annotations).  The
rest is plain interval arithmetic on those lists, kept apart so it can
be checked on a small synthetic trace:

  * busy time is the union of the device's operation intervals over the
    traced window; idle is the rest;
  * each idle gap is labelled by the harness span the host was in at
    its middle (a prefill call, a decode call, a pacing wait), or
    ``scheduler`` where the host was in none of them;
  * a kernel's time is the sum of the durations of its operations.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
SPAN_LABELS = {"bench.prefill": "prefill call", "bench.decode": "decode call",
               "bench.pacing": "pacing wait"}


@dataclasses.dataclass
class Op:
    name: str                     # the op's HLO text: "%name.N = ..."
    start: float                  # ns, on the trace's clock
    end: float
    device: int


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]

    @property
    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})

    def window(self) -> Tuple[float, float]:
        """The traced window: first to last harness span."""
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


def xplane_path(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            device = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns, ev.end_ns, device))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans)


# ------------------------------------------------------------ intervals


def union(intervals: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, device: int, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(
        [(o.start, o.end) for o in trace.ops if o.device == device], lo, hi))


def idle_gaps(trace: Trace, device: int, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    busy = union([(o.start, o.end) for o in trace.ops if o.device == device],
                 lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(gap: Tuple[float, float], spans: List[Span]) -> str:
    """The harness span the host was in at the gap's middle."""
    mid = (gap[0] + gap[1]) / 2.0
    inside = [s for s in spans if s.start <= mid < s.end]
    if not inside:
        return "scheduler"
    return SPAN_LABELS.get(inside[-1].name, inside[-1].name)


def longest_gaps(trace: Trace, device: int, lo: float, hi: float,
                 n: int = 10) -> List[Tuple[str, float]]:
    gaps = sorted(idle_gaps(trace, device, lo, hi),
                  key=lambda g: g[0] - g[1])[:n]
    return [(label(g, trace.spans), (g[1] - g[0]) / 1e9) for g in gaps]


def op_seconds(trace: Trace, match: Callable[[Op], bool] = lambda o: True
               ) -> float:
    return sum(o.end - o.start for o in trace.ops if match(o)) / 1e9


# ops that hold others (a loop, a call) would count their contents twice
PARENT_OPS = ("%while", "%conditional", "%call")
NAME_CHARS = 120


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The device operations that took most time, leaves only, by the
    start of their HLO text (instruction name, shape, opcode)."""
    total: Dict[str, float] = {}
    for o in trace.ops:
        if o.name.startswith(PARENT_OPS):
            continue
        key = o.name[:NAME_CHARS]
        total[key] = total.get(key, 0.0) + (o.end - o.start) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
