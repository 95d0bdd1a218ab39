"""Coded serving driver: batched requests through the ApproxIFER protocol.

Serves the paper's prediction-serving system end to end on host devices
through the event-driven scheduler (DESIGN.md §8): requests arrive on a
Poisson clock, the deadline-flushing batcher forms groups of K, groups
are Berrut-encoded, and every autoregressive round is a coded dispatch
whose straggler mask derives from per-worker completion times sampled
from the latency model — the decode fires the moment the fastest
``wait_for`` coded streams land.  With E > 0 a stateful adversary
(``--attack persistent|intermittent|colluding``) corrupts compromised
workers' logits at completion time; the vote-gated locator excludes
them, reputation accumulates, and (with ``--quarantine``) repeat
offenders stop being dispatched to until their probation expires.

Any registered redundancy scheme serves through the same event loop
(``--scheme berrut|nercc|invnet|parm|replication|uncoded``, DESIGN.md
§9/§14): "berrut" (default) drives the jitted autoregressive coded-LLM
path; the other schemes serve single-shot next-token prediction over
the model's embedding space via ``EngineExecutor`` — ParM parity
queries are sums of embeddings, replication copies them, NeRCC fits a
nested Chebyshev regression over the streams, Coded-InvNet mixes
flow-lifted queries into parity streams, and the decode recovers the
straggled slots per scheme.

With ``--continuous`` the berrut LLM path runs continuous batching over
a fixed coded-KV slot pool (DESIGN.md §10): ``--pool-groups`` group
slots host groups that join at prefill mid-flight and retire
independently at per-request generation budgets (drawn 1..steps so the
pool genuinely churns); prefill and decode-step trace exactly once for
the whole run, partial flushes included.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 16 --k 4 --s 1 --steps 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 32 --k 4 --steps 8 --continuous --pool-groups 4
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 16 --k 4 --e 1 --attack colluding --attack-rate 0.5 \
      --quarantine
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 16 --k 4 --scheme replication
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 32 --k 4 --s 1 --e 1 --adaptive --churn --traffic diurnal \
      --attack intermittent --attack-rate 0.3 --quarantine
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 32 --k 4 --s 1 --e 1 --adaptive --continuous \
      --pool-groups 4 --attack persistent --quarantine

With ``--adaptive`` a ``RedundancyController`` (DESIGN.md §12) watches
per-window straggler/attack rates and retunes (N, E, wait_for) between
batches, never letting the decode wait-for fall below the locator
quorum.  Adaptive redundancy reaches every serving path (DESIGN.md
§15): the berrut LLM executors — batch-scoped and ``--continuous``
slot-pool alike — trace ONE max-width program at the controller's
maximum operating point and mask narrower (N, E) points off in-program,
so a retune never recompiles.  ``--churn`` adds worker leave/rejoin on
exponential clocks and ``--traffic diurnal`` replaces the homogeneous
Poisson arrivals with a diurnal + bursty trace around ``--rate``.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core.scheme import get_scheme, scheme_names
from repro.launch.compile_cache import enable_compile_cache
from repro.models import embed_inputs, init_params
from repro.models import predict_fn as make_predict_fn
from repro.serving import (AdversaryConfig, ChurnModel, CodedLLMExecutor,
                           CodedScheduler, ContinuousConfig,
                           ContinuousLLMExecutor, ContinuousScheduler,
                           ControllerConfig, EngineExecutor, LatencyModel,
                           QuarantineConfig, RedundancyController,
                           SampleConfig, SchedulerConfig, TrafficModel,
                           percentile_table, trace_arrivals)


def run(arch: str, reduced: bool, requests: int, k: int, s: int, e: int,
        prompt_len: int, steps: int, byz_sigma: float, seed: int = 0,
        rate_rps: float = 2000.0, flush_deadline_ms: float = 5.0,
        groups_per_batch: int = 2, slo_ms: float | None = None,
        attack: str = "persistent", attack_rate: float = 1.0,
        attack_placement: str = "random", quarantine: bool = False,
        probation_ms: float = 200.0, scheme: str = "berrut",
        continuous: bool = False, pool_groups: int = 4,
        top_k: int = 1, temperature: float = 1.0,
        adaptive: bool = False, churn: bool = False,
        churn_up_ms: float = 2000.0, churn_down_ms: float = 200.0,
        traffic: str = "poisson"):
    cfg = configs.get_reduced(arch) if reduced else configs.get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(seed)

    schm = get_scheme(scheme, k=k, s=s, e=e)
    coding = getattr(schm, "coding", None)      # BerrutScheme only

    print(f"serving {requests} requests at {rate_rps:.0f} req/s as groups "
          f"of K={k} x {schm.num_workers} {scheme} worker streams "
          f"(overhead {schm.overhead:.2f}x; replication would need "
          f"{(s + 1) * k if e == 0 else (2 * e + 1) * k} workers/group, "
          f"uncoded {k})")
    if e and coding is not None:
        print(f"adaptive wait-for {coding.decode_quorum} of "
              f"{coding.num_workers} (locator quorum K+2E; paper offline "
              f"wait_for {coding.wait_for}), attack={attack} "
              f"rate={attack_rate} sigma={byz_sigma} "
              f"quarantine={'on' if quarantine else 'off'}")
    if scheme == "parm":
        print("parm: parity stream runs the hosted model on summed "
              "embeddings (no per-model distilled parity network here — "
              "exactly the retraining cost ApproxIFER removes)")
    if scheme == "nercc":
        locator = (f"; E={e} runs the studentised-residual vote locator"
                   if e else "")
        print("nercc: nested-regression coding (arXiv 2402.04377) — "
              "ridge Chebyshev encoder/decoder over Berrut's worker "
              f"geometry{locator}")
    if scheme == "invnet":
        print("invnet: Coded-InvNet (arXiv 2106.06445) — parity streams "
              "run the hosted model on flow-mixed queries; a single "
              "failed stream reconstructs exactly (trained-free "
              "fallback when no flow is fit)")

    if continuous and scheme != "berrut":
        raise ValueError("--continuous drives the jitted berrut slot-pool "
                         f"path; scheme {scheme!r} serves single-shot")
    # On-device token selection (DESIGN.md §11): the jitted steps return
    # (B,) int32 sampled ids, never round-tripping (B, V) logits.
    sample = SampleConfig(top_k=top_k, temperature=temperature)
    latency_model = LatencyModel()
    token_prompts = [rng.randint(0, cfg.vocab_size,
                                 (prompt_len,)).astype(np.int32)
                     for _ in range(requests)]
    budgets = None
    controller = None
    if adaptive:
        # bounds: one step of headroom above the CLI operating point on
        # each axis (E needs at least 1 so the locator can be grown in).
        # Built BEFORE the executor: the jitted LLM executors trace at
        # the controller's MAXIMUM operating point and mask narrower
        # points off in-program (DESIGN.md §15).
        controller = RedundancyController(schm, ControllerConfig(
            window_rounds=8, s_min=0, s_max=s + 1,
            e_min=0, e_max=max(e, 1)))
        pool = controller.pool
        print(f"adaptive redundancy: start (S={s}, E={e}), bounds "
              f"S<={s + 1} E<={max(e, 1)}, pool sized for "
              f"{pool.num_workers} workers (DESIGN.md §12/§15)")
    if scheme == "berrut" and continuous:
        # slot-pool continuous batching: mixed per-request generation
        # budgets (1..steps) make groups retire at different rounds, the
        # churn the fixed pool exists to absorb
        pool_coding = (controller.max_scheme.coding
                       if controller is not None else coding)
        executor = ContinuousLLMExecutor(
            cfg, pool_coding, params, pool_groups=pool_groups,
            max_len=prompt_len + steps + 2,
            byz_collude=(attack == "colluding" and e > 0),
            sample=sample, sample_seed=seed)
        payloads = token_prompts
        budgets = rng.randint(1, steps + 1, size=requests)
    elif scheme == "berrut":
        # jitted autoregressive coded-LLM path: payloads are token
        # prompts, every decode round is a coded dispatch; under
        # --adaptive the ONE traced program covers the max operating
        # point and retunes dispatch to a maskable prefix of its grid
        exec_coding = (controller.max_scheme.coding
                       if controller is not None else coding)
        executor = CodedLLMExecutor(cfg, exec_coding, params, steps=steps,
                                    max_len=prompt_len + steps + 2,
                                    seed=seed, sample=sample)
        payloads = token_prompts
    else:
        # scheme-generic single-shot path: payloads are residual-stream
        # embeddings (ParM's parity query is a SUM of queries, which is
        # only meaningful in a continuous input space), one next-token
        # prediction per request
        f = jax.jit(make_predict_fn(cfg, params))
        emb = embed_inputs(cfg, params,
                           {"tokens": jax.numpy.asarray(
                               np.stack(token_prompts))})
        payloads = [np.asarray(emb[i]) for i in range(requests)]
        executor = EngineExecutor(f, schm)
    # num_adversaries comes from the CLI --e, NOT scheme.e: schemes that
    # tolerate no Byzantine workers (uncoded) would otherwise silently
    # zero out the compromised set and the "defenseless baseline under
    # attack" run would measure an unattacked system.
    adversary = (AdversaryConfig(kind=attack, attack_rate=attack_rate,
                                 sigma=byz_sigma, num_adversaries=e,
                                 placement=attack_placement, seed=seed)
                 if e else None)
    # Quarantine needs locate verdicts to act on: schemes without an
    # error locator (replication median, uncoded) never produce any, so
    # the policy would run dead — refuse silently-inactive flags.
    if quarantine and e and not schm.has_locator:
        print(f"warning: --quarantine is inactive for scheme "
              f"{schm.name!r} (no error locator feeds the reputation "
              f"policy); ignoring")
        quarantine = False
    quarantine_cfg = (QuarantineConfig(probation_ms=probation_ms)
                      if quarantine and e else None)
    churn_model = (ChurnModel(mean_up_ms=churn_up_ms,
                              mean_down_ms=churn_down_ms, seed=seed + 7)
                   if churn else None)
    arrival_ms = None
    if traffic == "diurnal":
        # diurnal + bursty non-homogeneous Poisson trace; --rate is the
        # base (mean) rate the diurnal swing oscillates around
        arrival_ms = trace_arrivals(requests,
                                    TrafficModel(base_rate_rps=rate_rps),
                                    seed=seed + 11)
    # under adaptive berrut the executor runs the controller's MAX point,
    # not the CLI (s, e) point — declare no scheme and let the executor's
    # own win (the controller validates base-K compatibility)
    adaptive_llm = controller is not None and scheme == "berrut"
    if continuous:
        sched = ContinuousScheduler(
            ContinuousConfig(coding=None if adaptive_llm else coding,
                             pool_groups=pool_groups,
                             flush_deadline_ms=flush_deadline_ms,
                             slo_ms=slo_ms, seed=seed, adversary=adversary,
                             quarantine=quarantine_cfg, churn=churn_model,
                             controller=controller, max_new_tokens=steps),
            latency_model, executor)
        print(f"continuous batching over {pool_groups} group slots "
              f"({pool_groups * executor.coding.num_workers} pooled coded "
              f"streams), per-request budgets 1..{steps}")
    else:
        sched = CodedScheduler(
            SchedulerConfig(scheme=None if adaptive_llm else schm,
                            groups_per_batch=groups_per_batch,
                            flush_deadline_ms=flush_deadline_ms,
                            slo_ms=slo_ms, seed=seed, adversary=adversary,
                            quarantine=quarantine_cfg,
                            controller=controller, churn=churn_model),
            latency_model, executor)

    t0 = time.time()
    # arrivals come from the scheduler's own Poisson stream, which is
    # seeded independently of the worker-latency stream
    if continuous:
        metrics = sched.run(payloads, arrival_ms=arrival_ms,
                            rate_rps=None if arrival_ms is not None
                            else rate_rps, max_new_tokens=budgets)
    else:
        metrics = sched.run(payloads, arrival_ms=arrival_ms,
                            rate_rps=None if arrival_ms is not None
                            else rate_rps)
    wall = time.time() - t0

    print(metrics.format_table())
    if controller is not None:
        for d in controller.decisions:
            print(f"  retune @round {d.round_idx}: S={d.s} E={d.e} -> "
                  f"{d.num_workers} workers, wait_for {d.wait_for} "
                  f"({d.reason})")
    if continuous:
        print(f"{sched.rounds_run} pool rounds, wall {wall:.2f}s")
    else:
        per_round = np.asarray([w for b in sched.batches
                                for w in b.round_waits])
        print(f"per-round decode trigger: "
              f"p50 {np.percentile(per_round, 50):.1f}"
              f"ms  p99 {np.percentile(per_round, 99):.1f}ms "
              f"({len(per_round)} coded rounds, wall {wall:.2f}s)")
    none_p99 = percentile_table(latency_model, k, s,
                                trials=4000)["none"]["p99_ms"]
    print(f"uncoded wait-for-all worker p99 would be {none_p99:.1f}ms")

    uids = sorted(sched.results)
    if continuous:
        # variable-length generations: requests retire at their budgets
        for r in uids[:4]:
            print(f"  request {r}: {sched.results[r].tolist()}")
        return [sched.results[u] for u in uids]
    outs = np.stack([sched.results[u] for u in uids])
    if scheme == "berrut":
        # the jitted LLM paths (adaptive included) emit token matrices
        toks = outs
    else:
        # scheme-generic path served last-position logits: report the
        # greedy next token per request
        toks = np.argmax(outs, -1)[:, None]
    for r in uids[:4]:
        print(f"  request {r}: {toks[r].tolist()}")
    return toks


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=configs.list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--s", type=int, default=1)
    ap.add_argument("--e", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--scheme", default="berrut", choices=scheme_names(),
                    help="redundancy scheme served through the event loop "
                         "(berrut drives the autoregressive coded-LLM "
                         "path; others serve next-token prediction over "
                         "embeddings)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a fixed coded-KV slot "
                         "pool (berrut only; DESIGN.md §10)")
    ap.add_argument("--pool-groups", type=int, default=4,
                    help="group-slot capacity of the continuous pool")
    ap.add_argument("--top-k", type=int, default=1,
                    help="on-device sampling: 1 = greedy, > 1 samples "
                         "from the temperature-scaled top-k logits "
                         "(berrut LLM paths)")
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="softmax temperature for --top-k > 1")
    ap.add_argument("--byz-sigma", type=float, default=50.0)
    ap.add_argument("--attack", default="persistent",
                    choices=["persistent", "intermittent", "colluding"],
                    help="adversary behavior model (active when --e > 0)")
    ap.add_argument("--attack-rate", type=float, default=1.0,
                    help="per-dispatch corruption probability "
                         "(intermittent/colluding)")
    ap.add_argument("--attack-placement", default="random",
                    choices=["random", "worst_case"],
                    help="compromised-worker placement")
    ap.add_argument("--quarantine", action="store_true",
                    help="stop dispatching to repeatedly-located workers")
    ap.add_argument("--probation-ms", type=float, default=200.0,
                    help="quarantine duration before re-admission")
    ap.add_argument("--adaptive", action="store_true",
                    help="closed-loop (N, E, wait_for) retuning between "
                         "batches (DESIGN.md §12/§15); berrut keeps the "
                         "jitted LLM paths (masked max-width programs, "
                         "--continuous included), other schemes serve "
                         "single-shot through the scheme-generic executor")
    ap.add_argument("--churn", action="store_true",
                    help="workers leave/rejoin on their own exponential "
                         "clocks (spot preemption, deploys)")
    ap.add_argument("--churn-up-ms", type=float, default=2000.0,
                    help="mean worker uptime between leaves")
    ap.add_argument("--churn-down-ms", type=float, default=200.0,
                    help="mean downtime before rejoin")
    ap.add_argument("--traffic", default="poisson",
                    choices=["poisson", "diurnal"],
                    help="arrival process: homogeneous Poisson at --rate, "
                         "or a diurnal+bursty trace around --rate")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="Poisson arrival rate, requests/second")
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="batcher flush deadline")
    ap.add_argument("--groups", type=int, default=2,
                    help="query groups per dispatched batch")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="latency SLO for goodput accounting")
    args = ap.parse_args()
    run(args.arch, args.reduced, args.requests, args.k, args.s, args.e,
        args.prompt_len, args.steps, args.byz_sigma, rate_rps=args.rate,
        flush_deadline_ms=args.deadline_ms, groups_per_batch=args.groups,
        slo_ms=args.slo_ms, attack=args.attack,
        attack_rate=args.attack_rate,
        attack_placement=args.attack_placement,
        quarantine=args.quarantine, probation_ms=args.probation_ms,
        scheme=args.scheme, continuous=args.continuous,
        pool_groups=args.pool_groups, top_k=args.top_k,
        temperature=args.temperature, adaptive=args.adaptive,
        churn=args.churn, churn_up_ms=args.churn_up_ms,
        churn_down_ms=args.churn_down_ms, traffic=args.traffic)


if __name__ == "__main__":
    main()
