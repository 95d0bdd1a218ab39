"""What one measured window served, rebuilt from the harness's records.

The executor subclass records every ``prefill`` / ``decode`` call: its
host-clock span (ended after the sampled ids reached the host), the ids
it returned for every pool row, and the masks it was given.  The
scheduler's public ``trace`` says which groups each round admitted and
decoded, and ``groups`` holds each admitted group's requests.  From
those three this module rebuilds, per request, when each token reached
the host and what it was, and derives the end-to-end numbers.

Percentiles are ``numpy.percentile`` with linear interpolation, the
arithmetic ``serving/metrics.py`` uses.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Call:
    """One executor call, timed on the host clock (seconds)."""

    kind: str                     # "prefill" | "decode"
    t0: float
    t1: float
    tokens: np.ndarray            # (P*K,) int32 ids for every pool row
    group_mask: np.ndarray        # (P,) admitted / active slots
    mask: np.ndarray              # (N+1,) workers the round waited for
    attack: Optional[np.ndarray]  # (N+1,) workers corrupting, or None


@dataclasses.dataclass
class Served:
    """One request as the window saw it."""

    uid: int
    gid: int
    row: int                      # pool row (slot * K + index in group)
    due_s: float                  # host clock
    budget: int
    tokens: List[int]             # served ids, in order
    times: List[float]            # host clock at which each reached the host

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.budget


@dataclasses.dataclass
class GroupRun:
    """One admitted group: its rounds and the rows' fed tokens."""

    gid: int
    slot: int
    k: int
    calls: List[int]              # call index per round, from admission
    prompts: np.ndarray           # (K, prompt_len) incl. padding rows
    valid: np.ndarray             # (K,) real requests


def round_calls(trace: list, n_calls: int) -> List[dict]:
    """Per pool round: admitted / active gids and the indices of its
    prefill and decode calls (a round makes the prefill call first)."""
    rounds, c = [], 0
    for entry in trace:
        if entry[0] != "round":
            continue
        _, _, _, admitted, active, _ = entry
        r = {"admitted": admitted, "active": active, "prefill": None,
             "decode": None}
        if admitted:
            r["prefill"], c = c, c + 1
        if active:
            r["decode"], c = c, c + 1
        rounds.append(r)
    if c != n_calls:
        raise RuntimeError(f"scheduler trace implies {c} executor calls, "
                           f"the executor recorded {n_calls}")
    return rounds


def rebuild(trace: list, groups: list, calls: List[Call], k: int,
            t_start: float) -> tuple:
    """-> (served requests by uid, group runs by gid)."""
    rounds = round_calls(trace, len(calls))
    admitted_at: Dict[int, int] = {}
    active_calls: Dict[int, List[int]] = {}
    for i, r in enumerate(rounds):
        for gid in r["admitted"]:
            admitted_at[gid] = i
            active_calls[gid] = [r["prefill"]]
        for gid in r["active"]:
            active_calls[gid].append(r["decode"])
    served: Dict[int, Served] = {}
    runs: Dict[int, GroupRun] = {}
    for g in groups:
        if g.gid not in admitted_at:
            continue
        idx = active_calls[g.gid]
        runs[g.gid] = GroupRun(
            gid=g.gid, slot=g.slot, k=k, calls=idx,
            prompts=np.stack([np.asarray(r.payload, np.int32)
                              for r in g.plan.requests]),
            valid=np.asarray(g.plan.valid, bool))
        for i, req in enumerate(g.plan.requests):
            if not g.plan.valid[i]:
                continue
            row = g.slot * k + i
            budget = int(g.budget[i])
            mine = idx[:budget]
            served[req.uid] = Served(
                uid=req.uid, gid=g.gid, row=row,
                due_s=t_start + req.arrival_ms / 1e3, budget=budget,
                tokens=[int(calls[c].tokens[row]) for c in mine],
                times=[calls[c].t1 for c in mine])
    return served, runs


def live_groups(trace: list) -> List[int]:
    """Gids admitted and not freed by the end of the scheduler's trace:
    the groups whose caches the pool holds when the window closes."""
    live: Dict[int, None] = {}
    for entry in trace:
        if entry[0] == "admit":
            live[entry[1]] = None
        elif entry[0] == "free":
            live.pop(entry[1], None)
    return list(live)


def host_gaps_ms(calls: List[Call], pacing: list) -> List[float]:
    """Host milliseconds from each executor call's end to the next
    call's start, less the pacing waits (host-clock spans) between."""
    return [(nxt.t0 - prev.t1
             - sum(max(0.0, min(b, nxt.t0) - max(a, prev.t1))
                   for a, b in pacing)) * 1e3
            for prev, nxt in zip(calls, calls[1:])]


def percentile(values, q: float) -> Optional[float]:
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, q))


def ttfts_ms(requests_due_s: Dict[int, float], served: Dict[int, Served],
             t_end: float) -> np.ndarray:
    """Due -> first token on the host, over every request due in the
    window; one still waiting at the end counts with the wait it has had
    so far (a lower bound, never left out)."""
    out = []
    for uid, due in requests_due_s.items():
        if due > t_end:
            continue
        s = served.get(uid)
        first = s.times[0] if s is not None and s.tokens else None
        out.append(((first if first is not None else t_end) - due) * 1e3)
    return np.asarray(out, np.float64)


def itls_ms(served: Dict[int, Served]) -> np.ndarray:
    gaps = [np.diff(np.asarray(s.times)) * 1e3 for s in served.values()
            if len(s.times) > 1]
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def tokens_served(served: Dict[int, Served]) -> int:
    return sum(len(s.tokens) for s in served.values())
