"""Continuous-batching coded LLM serving over a fixed coded-KV slot pool
(DESIGN.md §10).

The run-to-completion scheduler (``serving.scheduler``) dispatches a
batch, decodes it for a fixed number of rounds, and only then touches
the queue — under real traffic with mixed generation lengths most of
the worker pool idles on requests that finished early, and a
deadline-flushed partial batch even changes the jitted shape and
recompiles.  This module replaces that lifecycle with a persistent
round loop over a fixed-capacity slot pool:

  * The jitted program ALWAYS runs ``pool_groups x (N+1)`` coded
    streams (``coded_serving.coded_pool_prefill`` /
    ``coded_pool_decode_step``); a group slot is live or free, never a
    different shape.  Prefill and decode-step each trace exactly once
    per serving run — no recompiles for partial batches, ever.
  * Groups join at prefill mid-flight: whenever slots are free and a
    group of K requests is ready (or its flush deadline expired), the
    next pool round admits it alongside the in-flight groups' decode.
  * Requests retire independently on per-request EOS /
    ``max_new_tokens``; a group's slots free when its last request
    retires, and freed slots are handed to queued groups on the next
    round.
  * Every stream decodes at its own cache depth (the per-slot ``pos``
    vector); the decode step hands those depths — and, for E == 0
    pools, the slot-live mask — to ``ops.pool_decode_attention``, whose
    Pallas kernel derives KV-tile validity in-kernel, so the pool never
    materialises a (streams, width) mask or full-width masked scores.

Every pool round is one coded dispatch: per-worker completion times are
sampled once, the round fires when the fastest ``wait_for`` coded
workers land, and the round's straggler mask (and Byzantine attack, if
an adversary is configured) applies to both the admissions' prefill and
the actives' decode step.  ``mode="run_to_completion"`` keeps the same
pool but only admits into an EMPTY pool — the batch-scoped baseline the
``--continuous`` benchmark compares against at an equal worker pool.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.berrut import CodingConfig
from repro.core.engine import mask_from_completion_times
from repro.core.scheme import BerrutScheme, as_scheme
from repro.serving.batcher import GroupBatcher
from repro.serving.coded_serving import (coded_pool_decode_step,
                                         coded_pool_prefill,
                                         init_pool_state)
from repro.serving.controller import RedundancyController
from repro.serving.failures import (AdversaryConfig, RoundAttack,
                                    make_adversary)
from repro.serving.latency import ChurnModel, LatencyModel, WorkerChurn
from repro.serving.metrics import RequestRecord, ServingMetrics
from repro.serving.quarantine import QuarantineConfig, WorkerReputation
from repro.serving.sampling import SampleConfig
from repro.serving.scheduler import (LocateReport, apply_pool_state,
                                     check_gather_bound,
                                     derive_seed_streams, resolve_arrivals,
                                     round_ground_truth)
from repro.serving.tracing import SpanLog

# Event kinds; numeric order breaks timestamp ties (arrivals land before
# a flush deadline at the same instant, which lands before a round).
_ARRIVAL, _FLUSH, _ROUND = 0, 1, 2

_MODES = ("continuous", "run_to_completion")


def _named(name: str, fn):
    """``fn`` under ``name``: jit names its program (and a profile its
    module) after the function, and a lambda is ``<lambda>``.  The
    lambdas look the step up in this module when traced, so a patched
    ``coded_pool_decode_step`` reaches the executor; a nested ``def``
    of that name would shadow it."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@dataclasses.dataclass(frozen=True)
class ContinuousConfig:
    """Knobs of the slot-pool serving runtime."""

    coding: Optional[CodingConfig] = None
    pool_groups: int = 4               # fixed group-slot capacity
    flush_deadline_ms: Optional[float] = 2.0
    slo_ms: Optional[float] = None     # goodput accounting only
    seed: int = 0
    wait_for: Optional[int] = None     # None -> scheme.decode_quorum
    adversary: Optional[AdversaryConfig] = None
    quarantine: Optional[QuarantineConfig] = None
    # worker churn on the event clock (DESIGN.md §12); a churned-out
    # worker's results never land, exactly like a quarantine hold.
    churn: Optional[ChurnModel] = None
    # Adaptive (N, E, wait_for) retuning between rounds (DESIGN.md §15):
    # the jitted pool shapes stay fixed at the controller's MAXIMUM
    # operating point (construct the executor at controller.max_scheme);
    # a narrower point masks off the beyond-width coded streams
    # in-program via the per-round live mask — no retrace, ever.
    controller: Optional["RedundancyController"] = None
    # "continuous": admit into free slots every round (the tentpole);
    # "run_to_completion": admit only into an EMPTY pool — the
    # batch-scoped baseline at the same pool/worker budget.
    mode: str = "continuous"
    max_new_tokens: int = 8            # default per-request budget
    eos_token_id: Optional[int] = None

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, got "
                             f"{self.mode!r}")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")


@dataclasses.dataclass
class SlotGroup:
    """One admitted group of K requests living in a pool slot."""

    gid: int
    slot: int
    plan: Any                          # BatchPlan (K requests, valid mask)
    admit_ms: float
    budget: np.ndarray                 # (K,) per-request max_new_tokens
    done: np.ndarray                   # (K,) bool (padding: done at birth)
    gen: np.ndarray                    # (K,) generated-token counts
    prefilled: bool = False
    deadline_flushed: bool = False


class ContinuousLLMExecutor:
    """Drives the jitted slot-pool serving steps behind the round loop.

    Wraps ``coded_pool_prefill`` / ``coded_pool_decode_step`` in TWO jit
    programs whose shapes are pinned to the pool
    (``pool_groups * (N+1)`` streams, fixed prompt length): admissions,
    retirements, deadline-flushed partial groups, and straggler /
    Byzantine masks are all data, so the whole serving run traces each
    program exactly once.  Byzantine arguments are normalized to
    zero-mask / zero-sigma arrays on clean rounds so the pytree
    structure (and therefore the compiled program) never changes;
    ``byz_collude`` is the one static — it must match the adversary's
    behavior model for the run.

    Perf contract (DESIGN.md §11): the ``CodedPoolState`` argument is
    DONATED to both jit programs, so XLA updates the pool KV caches in
    place instead of double-allocating the whole pool every round —
    callers must treat the state they passed in as consumed and only
    ever use the returned one.  Token selection runs on device
    (``SampleConfig``; greedy by default): ``prefill``/``decode``
    return (pool_groups*K,) int32 token ids, not (pool_groups*K, V)
    logits.

    Adaptive redundancy (DESIGN.md §15): construct the executor at the
    controller's MAXIMUM operating point (``controller.max_scheme``).
    The per-round ``live_mask`` masks off the coded streams beyond the
    current operating point's width in-program (composed into the
    straggler mask, exactly like a straggler), and ``locate_quorum`` is
    a traced per-round argument — both are normalized to constant-
    structure arrays (ones / int32 0, bit-identical defaults), so the
    two-traces-per-run contract survives every retune.
    """

    supports_replan = True

    def __init__(self, model_cfg, coding, params, pool_groups: int,
                 max_len: int, byz_collude: bool = False,
                 sample: Optional[SampleConfig] = None,
                 sample_seed: int = 0, wshard=None):
        self.scheme = as_scheme(coding)
        if not isinstance(self.scheme, BerrutScheme):
            raise TypeError("ContinuousLLMExecutor drives the jitted "
                            "Berrut slot-pool steps; use EngineExecutor "
                            f"for scheme {self.scheme.name!r}")
        coding = self.scheme.coding
        self.coding = coding
        self.model_cfg = model_cfg
        self.params = params
        self.pool_groups = pool_groups
        self.max_len = max_len
        self.byz_collude = byz_collude
        self.sample = sample if sample is not None else SampleConfig()
        # static worker-axis sharding config (DESIGN.md §13): baked into
        # both jit programs like ``coding`` — worker-major stream layout
        # + survivor-only gather inside, same donation/compile contracts
        self.wshard = wshard
        self._key = jax.random.PRNGKey(sample_seed)
        self.max_replan_workers = coding.num_workers
        sample_cfg = self.sample
        self._prefill = jax.jit(_named(
            "coded_pool_prefill",
            lambda p, st, t, a, m, bm, br, bs, sr, live, lq:
            coded_pool_prefill(
                model_cfg, coding, p, st, {"tokens": t}, max_len, a,
                straggler_mask=m, byz_mask=bm, byz_rng=br, byz_sigma=bs,
                byz_collude=byz_collude, with_report=True,
                sample=sample_cfg, sample_rng=sr, wshard=wshard,
                live_mask=live, locate_quorum=lq)),
            donate_argnums=(1,))
        self._decode = jax.jit(_named(
            "coded_pool_decode_step",
            lambda p, st, t, a, m, bm, br, bs, sr, live, lq:
            coded_pool_decode_step(
                model_cfg, coding, p, st, t, a,
                straggler_mask=m, byz_mask=bm, byz_rng=br, byz_sigma=bs,
                byz_collude=byz_collude, with_report=True,
                sample=sample_cfg, sample_rng=sr, wshard=wshard,
                live_mask=live, locate_quorum=lq)),
            donate_argnums=(1,))
        # host spans of every call (serving/tracing.py); the scheduler
        # writes its round spans here too
        self.spans = SpanLog()
        self._calls = 0

    def init_state(self):
        return init_pool_state(self.model_cfg, self.coding,
                               self.pool_groups, self.max_len)

    def _next_rng(self) -> jax.Array:
        """Per-round sampling key (unused by the greedy default, but
        always passed so the jit signature never changes)."""
        self._key, sub = jax.random.split(self._key)
        return sub

    def _byz_args(self, attack: Optional[RoundAttack]):
        """Constant-structure Byzantine args: a clean round is a
        zero-mask, zero-sigma attack, NOT a ``None`` (whose different
        pytree structure would force a second compilation)."""
        if attack is None or not attack.active:
            return (jnp.zeros((self.coding.num_workers,), jnp.float32),
                    jax.random.PRNGKey(0), jnp.asarray(0.0, jnp.float32))
        if bool(attack.collude) != self.byz_collude:
            raise ValueError(
                f"adversary collude={attack.collude} does not match the "
                f"executor's static byz_collude={self.byz_collude}")
        return (jnp.asarray(attack.mask, jnp.float32), attack.key,
                jnp.asarray(attack.sigma, jnp.float32))

    def _report(self, mask: np.ndarray, report) -> Optional[LocateReport]:
        if self.coding.e == 0:
            return None
        located, votes = report
        g = located.shape[0]
        located = np.asarray(located)
        return LocateReport(
            located=located, votes=np.asarray(votes),
            masks=np.broadcast_to(mask, (g, len(mask)))
            * (1.0 - located.astype(np.float32)))

    def _replan_args(self, live_mask, locate_quorum):
        """Constant-structure re-plan args: an all-live round with no
        quorum gate is ones / int32 0 — bit-identical defaults
        (``x * 1.0 == x``; ``sum(avail) >= 0`` is always true)."""
        live = (np.ones((self.coding.num_workers,), np.float32)
                if live_mask is None
                else np.asarray(live_mask, np.float32))
        lq = jnp.asarray(0 if locate_quorum is None else locate_quorum,
                         jnp.int32)
        return jnp.asarray(live), lq

    def _call(self, kind: str, program, state, rows: np.ndarray,
              group_mask: np.ndarray, mask: np.ndarray,
              attack: Optional[RoundAttack], live_mask, locate_quorum):
        """One jitted call, in the four spans ``exec.<kind>.prepare`` /
        ``.dispatch`` / ``.fetch`` / ``.report``."""
        spans = self.spans
        with spans.span(f"exec.{kind}", call=self._calls):
            self._calls += 1
            with spans.span(f"exec.{kind}.prepare"):
                args = (self.params, state, jnp.asarray(rows, jnp.int32),
                        jnp.asarray(group_mask, jnp.float32),
                        jnp.asarray(mask, jnp.float32),
                        *self._byz_args(attack), self._next_rng(),
                        *self._replan_args(live_mask, locate_quorum))
            with spans.span(f"exec.{kind}.dispatch"):
                tokens, state, report = program(*args)
            with spans.span(f"exec.{kind}.fetch"):
                tokens = np.asarray(tokens)
            with spans.span(f"exec.{kind}.report"):
                report = self._report(mask, report)
            # the inputs' device arrays are released inside the call's
            # span, not after it
            del args
        return tokens, state, report

    def prefill(self, state, prompts: np.ndarray, admit_mask: np.ndarray,
                mask: np.ndarray, attack: Optional[RoundAttack] = None,
                live_mask: Optional[np.ndarray] = None,
                locate_quorum: Optional[int] = None):
        """Consumes ``state`` (donated); returns ((P*K,) int32 sampled
        token ids, new state, locate report)."""
        return self._call("prefill", self._prefill, state, prompts,
                          admit_mask, mask, attack, live_mask, locate_quorum)

    def decode(self, state, tokens: np.ndarray, active_mask: np.ndarray,
               mask: np.ndarray, attack: Optional[RoundAttack] = None,
               live_mask: Optional[np.ndarray] = None,
               locate_quorum: Optional[int] = None):
        """Consumes ``state`` (donated); returns ((P*K,) int32 sampled
        token ids, new state, locate report)."""
        return self._call("decode", self._decode, state, tokens,
                          active_mask, mask, attack, live_mask,
                          locate_quorum)


class ContinuousScheduler:
    """Discrete-event round loop over the fixed coded-KV slot pool.

    ``run`` consumes per-request token prompts plus arrival times (and
    per-request generation budgets) and returns ``ServingMetrics``;
    per-request generated-token arrays land in ``results`` (keyed by
    uid, variable length — requests retire independently).  ``trace``
    is the golden event log: one tuple per admission / round / request
    retirement / slot free, in event order, bit-reproducible for a
    fixed seed.
    """

    def __init__(self, config: ContinuousConfig,
                 latency_model: LatencyModel,
                 executor: ContinuousLLMExecutor):
        self.config = config
        self.latency_model = latency_model
        self.executor = executor
        scheme = executor.scheme
        if (config.coding is not None
                and as_scheme(config.coding).config != scheme.config):
            raise ValueError(
                f"ContinuousConfig declares coding {config.coding} but "
                f"the executor runs {scheme.config}")
        if config.pool_groups != executor.pool_groups:
            raise ValueError(
                f"ContinuousConfig.pool_groups={config.pool_groups} but "
                f"the executor's pool has {executor.pool_groups} slots")
        self.scheme = scheme
        self.pool_groups = executor.pool_groups
        self.batcher = GroupBatcher(
            scheme, groups_per_batch=1,
            flush_deadline_ms=config.flush_deadline_ms)
        self.metrics = ServingMetrics(slo_ms=config.slo_ms)
        self.results: Dict[int, np.ndarray] = {}
        self.groups: List[SlotGroup] = []       # every admitted group
        self.trace: List[tuple] = []            # golden event log
        # per-round dispatch widths (== num_workers at the round's
        # operating point) — the adaptive benchmark's cost axis
        self.round_widths: List[int] = []
        self._wait_for = (scheme.decode_quorum if config.wait_for is None
                          else config.wait_for)
        self.controller = config.controller
        if self.controller is not None:
            if not getattr(executor, "supports_replan", False):
                raise ValueError(
                    "adaptive redundancy needs an executor that re-plans "
                    f"per round; {type(executor).__name__} cannot")
            base = self.controller.base
            if base.name != scheme.name or base.k != scheme.k:
                raise ValueError(
                    f"controller tunes scheme {base.name!r} K={base.k} "
                    f"but the executor runs {scheme.name!r} K={scheme.k}")
            if config.wait_for is not None:
                raise ValueError("wait_for is controller-managed under "
                                 "adaptive redundancy")
            max_w = getattr(executor, "max_replan_workers",
                            scheme.num_workers)
            if self.controller.pool.num_workers > max_w:
                raise ValueError(
                    f"the controller's maximum operating point dispatches "
                    f"{self.controller.pool.num_workers} workers but the "
                    f"executor's traced pool covers {max_w}: construct "
                    f"the executor at controller.max_scheme")
        wshard = getattr(executor, "wshard", None)
        if wshard is not None:
            # survivor-only decode keeps a static gather width; a round
            # waiting for MORE responses than that would silently truncate
            # survivors it paid latency for (DESIGN.md §13)
            bound = max(self._wait_for, scheme.decode_quorum)
            width = wshard.resolved_width(executor.coding)
            if width < bound:
                raise ValueError(
                    f"worker-shard gather width {width} < the pool's "
                    f"maximum wait-for {bound}: survivor-only decode would "
                    f"drop responses the round waited for — construct the "
                    f"executor with WorkerShardConfig(gather_width={bound})")
        if not 1 <= self._wait_for <= scheme.num_workers:
            raise ValueError(f"wait_for={self._wait_for} out of range for "
                             f"{scheme.num_workers} workers")
        self.adversary = make_adversary(scheme, config.adversary)
        if (self.adversary is not None
                and (config.adversary.kind == "colluding")
                != executor.byz_collude):
            raise ValueError(
                "executor byz_collude must be True exactly for the "
                "colluding adversary (it is jit-static)")
        self.reputation = (WorkerReputation(scheme, config.quarantine)
                           if config.quarantine is not None else None)
        self._churn = (WorkerChurn(config.churn, scheme.num_workers)
                       if config.churn is not None else None)
        self._rng, self._arrival_seed = derive_seed_streams(config.seed)
        self._events: list = []
        self._seq = itertools.count()
        self._gid = itertools.count()
        self._arrival_ms: Dict[int, float] = {}
        self._first_ms: Dict[int, float] = {}
        self._outs: Dict[int, list] = {}
        self._now = 0.0
        self._round_idx = 0
        self._inflight = False
        self._force = False
        self._slots: List[Optional[SlotGroup]] = [None] * self.pool_groups
        self._free: List[int] = list(range(self.pool_groups))
        self._state = executor.init_state()
        self._prompt_buf: Optional[np.ndarray] = None
        self._token_buf = np.zeros((self.pool_groups * scheme.k, 1),
                                   np.int32)

    # -- event plumbing --------------------------------------------------

    def _push(self, t: float, kind: int, data: Any) -> None:
        heapq.heappush(self._events, (t, kind, next(self._seq), data))

    def _occupied(self) -> bool:
        return any(g is not None for g in self._slots)

    @property
    def rounds_run(self) -> int:
        return self._round_idx

    def run(self, payloads: Sequence[np.ndarray],
            arrival_ms: Optional[Sequence[float]] = None,
            rate_rps: Optional[float] = None,
            max_new_tokens: Optional[Any] = None) -> ServingMetrics:
        """Serve ``payloads`` (uniform-length int32 token prompts).

        ``max_new_tokens``: scalar or per-request sequence of generation
        budgets (default ``config.max_new_tokens`` for all) — the mixed
        generation lengths continuous batching exists to exploit.
        """
        arrival_ms = resolve_arrivals(len(payloads), arrival_ms, rate_rps,
                                      self._arrival_seed)
        if max_new_tokens is None:
            budgets = [self.config.max_new_tokens] * len(payloads)
        elif np.ndim(max_new_tokens) == 0:
            budgets = [int(max_new_tokens)] * len(payloads)
        else:
            budgets = [int(b) for b in max_new_tokens]
            if len(budgets) != len(payloads):
                raise ValueError("max_new_tokens/payloads length mismatch")
        if any(b < 1 for b in budgets):
            raise ValueError("per-request max_new_tokens must be >= 1")
        shapes = {np.shape(p) for p in payloads}
        if len(shapes) != 1:
            raise ValueError(f"prompts must share one fixed shape (the "
                             f"jitted pool shape), got {sorted(shapes)}")
        (prompt_len,) = shapes.pop()
        self._prompt_buf = np.zeros(
            (self.pool_groups * self.scheme.k, prompt_len), np.int32)
        for t, payload, budget in zip(arrival_ms, payloads, budgets):
            self._push(float(t), _ARRIVAL, (payload, budget))
        while self._events or len(self.batcher) or self._occupied():
            if not self._events:
                # arrivals exhausted with no flush deadline configured:
                # admit the remaining partial group at the current clock
                self._try_start_round(self._now, force=True)
                if not self._events:
                    break
                continue
            t, kind, _, data = heapq.heappop(self._events)
            self._now = max(self._now, t)
            if kind == _ARRIVAL:
                self._on_arrival(t, data)
            elif kind == _FLUSH:
                self._on_flush(t, data)
            elif kind == _ROUND:
                self._on_round(t, data)
        if self.reputation is not None:
            counts = self.reputation.counts()
            self.metrics.quarantine_events = counts["quarantines"]
            self.metrics.readmissions = counts["readmissions"]
            self.metrics.early_readmissions = counts["early_readmissions"]
        if self._churn is not None:
            leaves, joins = self._churn.events_until(self._now)
            self.metrics.churn_leaves = leaves
            self.metrics.churn_joins = joins
        return self.metrics

    # -- handlers --------------------------------------------------------

    def _on_arrival(self, t: float, data) -> None:
        payload, budget = data
        uid = self.batcher.submit(payload, now=t, max_new_tokens=budget)
        self._arrival_ms[uid] = t
        self._outs[uid] = []
        self._try_start_round(t)
        if self.batcher.flush_deadline_ms is not None and uid in \
                self.batcher.pending_uids():
            self._push(t + self.batcher.flush_deadline_ms, _FLUSH, uid)

    def _on_flush(self, t: float, uid: int) -> None:
        # if the round loop is spinning, the deadline check happens at
        # the next round boundary anyway; when idle, this event wakes it
        if not self._inflight and self.batcher.deadline_expired(t):
            self._try_start_round(t)

    def _admit(self, now: float) -> List[SlotGroup]:
        """Move ready (or deadline-expired) groups into free slots."""
        if (self.config.mode == "run_to_completion" and self._occupied()):
            return []                   # batch-scoped baseline: drain first
        admitted: List[SlotGroup] = []
        k = self.scheme.k
        while self._free:
            flush = self._force or self.batcher.deadline_expired(now)
            plan = self.batcher.take_group(flush=flush)
            if plan is None:
                break
            slot = self._free.pop(0)
            n_valid = int(plan.valid.sum())
            group = SlotGroup(
                gid=next(self._gid), slot=slot, plan=plan, admit_ms=now,
                budget=np.asarray(
                    [r.max_new_tokens or self.config.max_new_tokens
                     for r in plan.requests], np.int64),
                done=~plan.valid.copy(), gen=np.zeros((k,), np.int64),
                deadline_flushed=n_valid < k)
            rows = slice(slot * k, (slot + 1) * k)
            self._prompt_buf[rows] = np.stack(
                [np.asarray(r.payload, np.int32) for r in plan.requests])
            self._slots[slot] = group
            self.groups.append(group)
            admitted.append(group)
            self.metrics.batches += 1
            if group.deadline_flushed:
                self.metrics.deadline_flushes += 1
            self.trace.append(("admit", group.gid, slot, now,
                               tuple(plan.uids), group.deadline_flushed))
        return admitted

    def _try_start_round(self, now: float, force: bool = False) -> None:
        if self._inflight:
            return
        # an attempt that starts no round leaves a span without ids
        with self.executor.spans.span("sched.start") as ids:
            self._start_round(now, force, ids)

    def _start_round(self, now: float, force: bool, ids: dict) -> None:
        self._force = force
        admitted = self._admit(now)
        self._force = False
        active = [g for g in self._slots if g is not None and g.prefilled]
        if not admitted and not active:
            return
        ids["round"] = self._round_idx
        ids["admitted"] = tuple(g.gid for g in admitted)
        full = self.scheme.num_workers
        # the round's operating point is pinned here: the controller may
        # retune BETWEEN rounds, never under one.  A narrower point
        # dispatches to a PREFIX of the traced max-width pool; the
        # beyond-width streams are masked off in-program (DESIGN.md §15).
        if self.controller is not None:
            point = self.controller.scheme
            wait_target = self.controller.wait_for
        else:
            point, wait_target = self.scheme, self._wait_for
        width = point.num_workers
        # latency draws always cover the widest pool (adaptive rounds
        # slice a prefix), so the RNG stream — and the golden trace —
        # does not depend on the controller's decisions
        with self.executor.spans.span("sched.latency"):
            times = self.latency_model.sample(self._rng, full)
        # quarantined / churned-out workers are pre-masked out of the
        # wait-for selection; the quorum invariant (apply_pool_state,
        # DESIGN.md §12) early-readmits held workers rather than let the
        # round silently wait below the K+2E locator quorum
        wait, times_w, degraded, locate_quorum = apply_pool_state(
            point, wait_target, times[:width], now,
            reputation=self.reputation, churn=self._churn)
        if degraded:
            self.metrics.degraded_rounds += 1
        mask_w, trigger = mask_from_completion_times(point, times_w,
                                                     wait_for=wait)
        attack = (self.adversary.next_round()
                  if self.adversary is not None else None)
        # the round's mask/attack live at the traced pool width; streams
        # beyond the operating point are not dispatched (mask 0), so the
        # adversary cannot corrupt through them either
        mask = np.zeros((full,), np.float32)
        mask[:width] = mask_w
        if attack is not None and width < full:
            am = np.array(attack.mask, np.float32)
            am[width:] = 0.0
            attack = dataclasses.replace(attack, mask=am)
        self._inflight = True
        self.round_widths.append(width)
        self.trace.append(("round", self._round_idx, now,
                           tuple(g.gid for g in admitted),
                           tuple(g.gid for g in active),
                           tuple(np.flatnonzero(mask).tolist())))
        self._push(now + float(trigger), _ROUND,
                   (admitted, active, mask, attack, width, locate_quorum,
                    times_w, float(trigger)))

    def _on_round(self, t: float, data) -> None:
        with self.executor.spans.span("sched.round", round=self._round_idx):
            self._run_round(t, data)
        self._try_start_round(t)

    def _run_round(self, t: float, data) -> None:
        (admitted, active, mask, attack, width, locate_quorum, times_w,
         trigger) = data
        self._inflight = False
        self.metrics.rounds += 1
        pool = self.pool_groups
        live = (np.arange(self.scheme.num_workers) < width).astype(
            np.float32)
        reports = []
        if admitted:
            admit_mask = np.zeros((pool,), np.float32)
            admit_mask[[g.slot for g in admitted]] = 1.0
            tokens, self._state, report = self.executor.prefill(
                self._state, self._prompt_buf, admit_mask, mask, attack,
                live_mask=live, locate_quorum=locate_quorum)
            reports.append((report, admit_mask))
            for g in admitted:
                g.prefilled = True
                self._emit(g, tokens, t, first=True)
        if active:
            act_mask = np.zeros((pool,), np.float32)
            act_mask[[g.slot for g in active]] = 1.0
            tokens, self._state, report = self.executor.decode(
                self._state, self._token_buf, act_mask, mask, attack,
                live_mask=live, locate_quorum=locate_quorum)
            self.metrics.decoded_rows += sum(int((~g.done).sum())
                                             for g in active)
            reports.append((report, act_mask))
            for g in active:
                self._emit(g, tokens, t, first=False)
        self._observe(t, mask, attack, reports)
        self._control(t, times_w, trigger, reports)
        for g in admitted + active:
            if g.done.all() and self._slots[g.slot] is g:
                self._slots[g.slot] = None
                self._free.append(g.slot)
                self._free.sort()
                self.trace.append(("free", g.gid, g.slot, t))
        self._round_idx += 1

    def _emit(self, group: SlotGroup, tokens: np.ndarray, t: float,
              first: bool) -> None:
        """Consume this round's on-device-sampled token column for one
        group; retire requests that hit their budget or EOS.  ``tokens``
        is the (pool_groups*K,) int32 id vector the executor returned —
        token selection already happened inside the jitted step, so the
        only per-round device->host traffic is this id vector."""
        k = self.scheme.k
        rows = slice(group.slot * k, (group.slot + 1) * k)
        toks = tokens[rows].astype(np.int32)
        live = ~group.done                       # before this round's token
        self._token_buf[rows, 0] = toks
        eos = self.config.eos_token_id
        for i, req in enumerate(group.plan.requests):
            if not live[i]:
                continue
            uid = req.uid
            self._outs[uid].append(int(toks[i]))
            group.gen[i] += 1
            if first:
                self._first_ms[uid] = t
            if group.gen[i] >= group.budget[i] or \
                    (eos is not None and int(toks[i]) == eos):
                group.done[i] = True
                self.results[uid] = np.asarray(self._outs[uid], np.int32)
                self.trace.append(("retire", uid, group.gid, t,
                                   int(group.gen[i])))
                self.metrics.record(RequestRecord(
                    uid=uid,
                    arrival_ms=self._arrival_ms[uid],
                    dispatch_ms=group.admit_ms,
                    complete_ms=t,
                    first_token_ms=self._first_ms[uid],
                    tokens=int(group.gen[i])))

    def _observe(self, t: float, mask: np.ndarray,
                 attack: Optional[RoundAttack],
                 reports: List[tuple]) -> None:
        """Score ONE locate observation for the whole pool round.

        A mixed round issues two jitted calls (admissions' prefill +
        actives' decode) but is still one coded dispatch — one mask, one
        attack — so their reports merge into a single observation: a
        second strike per round would quarantine workers twice as fast
        as the legacy scheduler under an identical config.  Each
        in-program report is already composed with its live-slot mask
        (free slots locate nothing); the per-call group mask restricts
        the corrupted-decode check to rows that were actually decoded —
        corruption "surviving" into a free slot's zeroed logits is not a
        robustness failure.
        """
        reports = [(r, gm) for r, gm in reports if r is not None]
        if not reports:
            return
        dispatched, true_corrupt = round_ground_truth(mask, attack)
        # a slot is admitted OR active in a round, never both, so the
        # reports' live rows are disjoint and merge by union
        detected = np.zeros_like(dispatched)
        decode_corrupt = False
        for report, group_mask in reports:
            detected |= report.detected
            live = group_mask >= 0.5
            decode_corrupt |= bool(
                np.any((report.masks[live] >= 0.5) & true_corrupt[None, :]))
        self.metrics.observe_locate(detected, true_corrupt, decode_corrupt)
        if self.reputation is not None:
            self.reputation.observe(t, detected, dispatched)

    def _control(self, t: float, times_w: np.ndarray, trigger: float,
                 reports: List[tuple]) -> None:
        """Feed one pool round's telemetry to the adaptive controller.

        The mixed round's per-call reports merge into ONE observation
        (concatenated along the group axis — ``detected`` is their
        union), mirroring ``_observe``: one coded dispatch, one strike.
        ``times_w`` are the operating point's sliced completion times,
        so the straggle statistic matches what the round dispatched.
        """
        if self.controller is None:
            return
        live = [r for r, _ in reports if r is not None]
        merged = None
        if live:
            merged = LocateReport(
                located=np.concatenate([r.located for r in live]),
                votes=np.concatenate([r.votes for r in live]),
                masks=np.concatenate([r.masks for r in live]))
        before = len(self.controller.decisions)
        held = (int(self.reputation.quarantined.sum())
                if self.reputation is not None else 0)
        decision = self.controller.observe_round(
            t, times=times_w, trigger_ms=trigger, report=merged,
            quarantined=held)
        self.metrics.control_decisions += \
            len(self.controller.decisions) - before
        if decision is not None:
            check_gather_bound(self.executor, decision.wait_for)
            self.trace.append(("retune", t, decision.num_workers,
                               decision.e, decision.wait_for))
