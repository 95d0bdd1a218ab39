"""Is what the timed path served right?  Compared after the window.

A sample of the requests the window finished, drawn from the seed and
always holding the longest, is read against the plain reference
(``reference.py``): each group they belong to is rebuilt from the
records of the timed calls (its prompts, the ids served back to every
row, the workers each round used), run once through the reference, and
every served token of the sampled requests is scored by how far its
logit lies below the reference's best at that position.  The gaps are
valid for greedy serving, which the program runs here.  The executor's
two programs end in on-device sampling and hand back ids only, so the
ids, and not the logits behind them, are what the timed path serves.

The timed path also leaves the pool state behind: the KV cache of every
group still in the pool when the window closes.  Its first layer (the
Berrut-coded embeddings, norm, projections, q/k norm, rotary, and the
cache writes of the prefill and of every decode round) is read against
the reference's, as a relative error.  On the TPU every float32 product
is one bfloat16 pass, so where two computations differ by a rounding an
operand can round the other way, and through the layers the ids and the
deeper caches drift from any reference as far as bfloat16 storage would;
the first layer does not drift, so bfloat16 storage shows there.

The readings named in the cell's limits are the numbers compared.
"""

from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

import reference
from model import Coding
from window import Call, GroupRun, Served

TARGET_TOKENS = 400           # served tokens compared, at least ...
MAX_GROUPS = 6                # ... from at most this many groups


def sample_groups(served: Dict[int, Served], seed: int) -> List[int]:
    """Group ids to compare: the longest finished request's first, then
    groups drawn from the seed until enough tokens are covered."""
    done = [s for s in served.values() if s.finished]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.tokens), -s.uid))
    per_group: Dict[int, int] = {}
    for s in done:
        per_group[s.gid] = per_group.get(s.gid, 0) + len(s.tokens)
    chosen = [longest.gid]
    covered = per_group[longest.gid]
    rest = sorted(g for g in per_group if g != longest.gid)
    for g in np.random.RandomState(seed).permutation(rest):
        if covered >= TARGET_TOKENS or len(chosen) >= MAX_GROUPS:
            break
        chosen.append(int(g))
        covered += per_group[int(g)]
    return chosen


def group_inputs(run: GroupRun, calls: List[Call], rows: List[Served]):
    """(tokens (K, T), used (J, N+1), served (K, J)) of one group, up to
    the longest of its compared requests."""
    j = max(len(s.tokens) for s in rows)
    k = run.k
    sl = slice(run.slot * k, (run.slot + 1) * k)
    outs = np.stack([calls[c].tokens[sl] for c in run.calls[:j]], axis=1)
    tokens = np.concatenate([run.prompts, outs[:, :j - 1]], axis=1)
    used = []
    for c in run.calls[:j]:
        u = calls[c].mask > 0.5
        if calls[c].attack is not None:
            u &= ~(calls[c].attack > 0.5)
        used.append(u)
    return tokens, np.stack(used), outs


def program_kv(arch, state, runs: Dict[int, GroupRun], gids: List[int],
               workers: int) -> Dict[int, tuple]:
    """First-layer cached (keys, values), each (N+1, P, kv_heads,
    head_dim), of the groups ``gids`` as the pool state the window ended
    with holds them (where ``arch.program_first_kv`` finds them), P the
    positions each has written: its prompt, then one per decode round.
    A group admitted after the last call has written nothing and is left
    out."""
    out = {}
    for gid in gids:
        run = runs.get(gid)
        if run is None:
            continue
        p = run.prompts.shape[1] + len(run.calls) - 1
        sl = slice(run.slot * workers, (run.slot + 1) * workers)
        out[gid] = tuple(np.asarray(a, np.float32)
                         for a in arch.program_first_kv(state, sl, p))
    return out


def kv_rel_err(got: tuple, ref: tuple) -> float:
    """The larger of the keys' and the values' relative error, each the
    norm of the difference over the reference's norm."""
    return max(float(np.linalg.norm(g - r) / np.linalg.norm(r))
               for g, r in zip(got, ref))


def compare(arch, dims, coding: Coding, params: dict, calls: List[Call],
            served: Dict[int, Served], runs: Dict[int, GroupRun],
            results: Dict[int, np.ndarray], kv: Dict[int, tuple],
            length: int, seed: int, references=(reference.REFERENCE,),
            controls=()) -> dict:
    """Readings of one run: how many finished requests' ids differ from
    the scheduler's own results (``"result_mismatches"``), and, by who
    and by reference (``out["program"][ref]``), for the program and each
    of ``controls`` (the reference in the program's place, in those
    numerics): the gap readings (``gap_readings``) of the served ids, and
    ``kv_rel_err``, the largest relative error of the first layer's
    cache over the groups in ``kv`` (``program_kv``)."""
    mismatches = sum(
        1 for s in served.values() if s.finished
        and not np.array_equal(np.asarray(s.tokens, np.int32),
                               np.asarray(results.get(s.uid, []), np.int32)))
    gaps: Dict[tuple, List[np.ndarray]] = {
        (w, r): [] for w in ("program", *controls) for r in references}
    for gid in sample_groups(served, seed):
        run = runs[gid]
        rows = [s for s in served.values() if s.gid == gid and s.finished]
        tokens, used, outs = group_inputs(run, calls, rows)
        got = reference.group_gaps(arch, dims, coding, params, tokens, used,
                                   outs, length, references=references,
                                   controls=controls)
        for s in rows:
            i = s.row - run.slot * run.k
            for key, g in got.items():
                gaps[key].append(g[i, :len(s.tokens)])
    out = {"result_mismatches": mismatches}
    for (w, r), g in gaps.items():
        out.setdefault(w, {})[r] = gap_readings(g)
    enc = jnp.asarray(reference.encode_matrix(coding), jnp.float32)
    errs: Dict[tuple, List[float]] = {key: [] for key in gaps}
    for gid, got in kv.items():
        run = runs[gid]
        p = got[0].shape[1]
        # fed ids: the prompt, then what each call but the last served
        tokens = np.zeros((run.k, length), np.int32)
        tokens[:, :run.prompts.shape[1]] = run.prompts
        for i, c in enumerate(run.calls[:-1]):
            tokens[:, run.prompts.shape[1] + i] = \
                calls[c].tokens[run.slot * run.k:(run.slot + 1) * run.k]
        side = {n: tuple(np.asarray(a[:, :p]) for a in
                         reference.first_layer_kv(arch, dims, params,
                                                  jnp.asarray(tokens), enc,
                                                  n))
                for n in dict.fromkeys(tuple(references) + tuple(controls))}
        for w, r in errs:
            errs[(w, r)].append(kv_rel_err(got if w == "program"
                                           else side[w], side[r]))
    for (w, r), e in errs.items():
        out[w][r]["kv_rel_err"] = max(e) if e else None
        out[w][r]["kv_groups"] = len(e)
    return out


def gap_readings(gaps: List[np.ndarray]) -> dict:
    """The widest and the mean gap, and the share of tokens that are not
    the reference's best, over all compared tokens."""
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    return {"max_logit_gap": float(g.max()) if g.size else 0.0,
            "mean_logit_gap": float(g.mean()) if g.size else 0.0,
            "not_best_share": float((g > 0).mean()) if g.size else 0.0,
            "tokens_compared": int(g.size)}


def verdict(readings: dict, mismatches: int, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) of one side's readings
    against the cell's limits: each reading named in ``limits`` at most
    its limit, ``min_tokens_compared`` tokens and one group's cache
    compared at least, and no served id other than the scheduler's."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items()
              if name != "min_tokens_compared"}
    checks["result_mismatches"] = {"value": mismatches, "limit": 0}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    checks["tokens_compared_min"] = {
        "value": readings["tokens_compared"],
        "limit": limits["min_tokens_compared"]}
    checks["kv_groups_min"] = {"value": readings["kv_groups"], "limit": 1}
    ok = (ok and readings["tokens_compared"] >= limits["min_tokens_compared"]
          and readings["kv_groups"] >= 1)
    return ok, checks
