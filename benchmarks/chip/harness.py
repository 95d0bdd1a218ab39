"""One run of one cell: set-up, the measured window, the check, the line.

  1. weights made on the device from the seed (``weights.py``), laid
     out by the configuration's architecture module (``archs/``);
  2. the executor's two programs (pool prefill, decode step) compiled
     or loaded from the checkout's compile cache, and warmed up by a
     short serving run at the cell's shapes;
  3. ``ContinuousScheduler.run`` over the cell's traffic for the window,
     its event clock bound to the host clock (``driver.py``);
  4. the end-to-end metrics (``--trace 0``) or, from a profiler trace of
     a sub-window and the harness's spans, the per-layer metrics
     (``--trace 1``);
  5. what the window served, and the first layer of the cache it left,
     compared with the plain reference (``correct.py``), after the
     program's state is freed;
  6. one JSON line, last on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import types
from typing import List, Optional

import numpy as np

import spec
import traffic as traffic_mod
from model import coding as coding_of

TRACE_DIR = spec.ROOT / ".bench_trace"
TRACE_START = 0.3             # share of the window before the trace starts
TRACE_SECONDS = 3.0           # traced sub-window (or half the window)
WARMUP_BUDGET = 3             # tokens per request of the warm-up run


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache hit is not one),
    and the jaxpr traces that come before a compile or a cache load."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.n = 0
        self.traces = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1
        elif event == self.TRACE:
            self.traces += 1


def require_devices(chips: int) -> list:
    """The accelerator the cell asks for, or exit without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip benchmark: no TPU (jax.devices()[0] is "
                         f"{devices[0].platform!r}); nothing measured")
    if len(devices) < chips:
        raise SystemExit(f"chip benchmark: the cell needs {chips} chips, "
                         f"JAX finds {len(devices)}")
    return devices


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for each consumer, from one --seed."""
    s = np.random.SeedSequence(seed).generate_state(6)
    names = ("weights", "traffic", "scheduler", "sampling", "adversary",
             "sample")
    return {n: int(v) & 0x7FFFFFFF for n, v in zip(names, s)}


def max_len(traffic: dict) -> int:
    return int(traffic["prompt_len"]) + int(traffic["output_tokens"]["max"]) + 2


def build(cell: spec.Cell, seed_of: dict):
    """(dims, coding, params, executor, scheduler factory)."""
    import jax
    from repro.core.berrut import CodingConfig
    from repro.models import init_params
    from repro.serving import SampleConfig
    import driver
    import weights
    dims, coding = cell.arch.dims(cell.config), coding_of(cell.config)
    cfg = cell.arch.program_model(cell.config)
    params = weights.make(cell.arch, dims, seed_of["weights"])
    weights.check_layout(params, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    executor = driver.TimedExecutor(
        cfg, CodingConfig(k=coding.k, s=coding.s, e=coding.e), params,
        pool_groups=int(cell.cell["pool_groups"]),
        max_len=max_len(cell.traffic), sample=SampleConfig(),
        sample_seed=seed_of["sampling"])
    return (dims, coding, params, executor,
            build_scheduler(cell, executor, coding, seed_of))


def build_scheduler(cell: spec.Cell, executor, coding, seed_of: dict):
    """A factory of (scheduler, host clock) pairs over ``executor``."""
    from repro.serving import (AdversaryConfig, ContinuousConfig,
                               ContinuousScheduler, LatencyModel)
    import driver
    adv = cell.config.get("adversary")
    adversary = (AdversaryConfig(kind=adv["kind"],
                                 num_adversaries=adv["workers"],
                                 sigma=adv["sigma"],
                                 seed=seed_of["adversary"])
                 if adv else None)

    def scheduler():
        clock = driver.HostClock(LatencyModel(), coding.quorum)
        sched = ContinuousScheduler(ContinuousConfig(
            coding=executor.coding, pool_groups=executor.pool_groups,
            flush_deadline_ms=cell.traffic["flush_deadline_ms"],
            seed=seed_of["scheduler"], adversary=adversary,
            max_new_tokens=1), clock, executor)
        return sched, clock

    return scheduler


def warm_up(executor, scheduler, prompt_len: int, k: int, vocab: int):
    """A short serving run at the cell's shapes: compiles (or loads)
    both programs and every small op the round loop uses."""
    sched, _ = scheduler()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, (prompt_len,)).astype(np.int32)
               for _ in range(k)]
    sched.run(prompts, arrival_ms=[0.0] * k, max_new_tokens=WARMUP_BUDGET)
    executor.calls.clear()
    executor.state = None
    # the warm-up's durations include compiles: no estimate for round 1
    executor.forget_durations()
    del sched
    gc.collect()


def memory_peak(devices: list) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_window(executor, scheduler, requests, seconds: float,
               tracer=None):
    """The measured window.  Returns (scheduler, clock record, t_start,
    t_end)."""
    import driver
    sched, clock = scheduler()
    # no collector pass inside the window: what set-up made is frozen out
    # of the heap it scans, and the window's own garbage waits for its end
    gc.collect()
    gc.freeze()
    gc.disable()
    t_start = time.perf_counter()
    clock.attach(sched, executor, t_start, seconds, tracer)
    try:
        sched.run(list(requests.prompts), arrival_ms=requests.arrival_ms,
                  max_new_tokens=requests.budgets)
    except driver.WindowClosed:
        pass
    finally:
        if tracer is not None:
            tracer.stop()
        gc.enable()
        gc.unfreeze()
    t_end = max(time.perf_counter(), t_start + seconds)
    return sched, clock.record, t_start, t_end


def stalls(calls, clock) -> dict:
    """The longest executor call of each kind and the longest host time
    between two calls less pacing waits, in ms: where a slow round went."""
    import window
    out = {}
    for kind in ("prefill", "decode"):
        d = [c.t1 - c.t0 for c in calls if c.kind == kind]
        if d:
            out[kind] = round(1e3 * max(d), 3)
    gaps = window.host_gaps_ms(calls, clock["pacing"])
    if gaps:
        out["between_calls"] = round(max(gaps), 3)
    return out


def end_to_end(ctx) -> dict:
    """Every end-to-end metric this harness measures, by name."""
    import window
    out = {"setup_s": ctx.setup_s}
    out["tokens_per_s"] = (window.tokens_served(ctx.served)
                           / ctx.window_s / ctx.chips)
    due = {i: ctx.t_start + a / 1e3
           for i, a in enumerate(ctx.requests.arrival_ms)}
    out["ttft_ms_p95"] = window.percentile(
        window.ttfts_ms(due, ctx.served, ctx.t_end), 95.0)
    out["itl_ms_p95"] = window.percentile(window.itls_ms(ctx.served), 95.0)
    return out


def reduce_trace(ctx):
    """Load the traced sub-window; None where nothing was traced."""
    import trace_reduce
    if ctx.tracer is None or ctx.tracer.t_on is None:
        return None
    trace = trace_reduce.load(trace_reduce.xplane_path(str(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if not trace.ops or not trace.spans:
        return None
    return trace


def breakdown(trace) -> dict:
    import trace_reduce
    lo, hi = trace.window()
    dev = trace.devices[0]
    return {"device_ops": [list(x) for x in trace_reduce.top_ops(trace)],
            "idle_gaps": [list(x) for x in
                          trace_reduce.longest_gaps(trace, dev, lo, hi)]}


def device_busy(trace) -> tuple:
    """(busy seconds averaged over the chips, traced window seconds)."""
    import trace_reduce
    lo, hi = trace.window()
    busy = [trace_reduce.busy_ns(trace, d, lo, hi) for d in trace.devices]
    return float(np.mean(busy)) / 1e9, (hi - lo) / 1e9


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_process: float, log=print) -> dict:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    import correct
    import driver
    import peaks as peaks_mod
    import reference
    import window

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    seed_of = seeds(seed)
    dims, coding, params, executor, scheduler = build(cell, seed_of)
    prompt_len = int(cell.traffic["prompt_len"])
    warm_up(executor, scheduler, prompt_len, coding.k, dims.vocab)
    requests = traffic_mod.generate(
        cell.traffic, cell.cell.get("rate_rps"), seconds, dims.vocab,
        seed_of["traffic"])
    tracer = None
    if trace:
        # the profiler's first start is slow: pay it in set-up
        jax.profiler.start_trace(str(TRACE_DIR))
        jax.profiler.stop_trace()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        start = TRACE_START * seconds
        tracer = driver.Tracer(str(TRACE_DIR), start,
                               start + min(TRACE_SECONDS, seconds / 2))
    before = compiles.n, compiles.traces
    sched, clock, t_start, t_end = run_window(executor, scheduler, requests,
                                              seconds, tracer)
    setup_s = t_start - t_process
    in_window = compiles.n - before[0]
    traced_in_window = compiles.traces - before[1]
    served, runs = window.rebuild(sched.trace, sched.groups, executor.calls,
                                  coding.k, t_start)
    ctx = types.SimpleNamespace(
        cell=cell, arch=cell.arch, dims=dims, coding=coding,
        chips=cell.chips, setup_s=setup_s, t_start=t_start, t_end=t_end,
        # a traced run's host-clock shares leave the tracer's own time out
        window_s=t_end - t_start - (tracer.cost_s if tracer else 0.0),
        calls=executor.calls, served=served, runs=runs, clock=clock,
        requests=requests, prompt_len=prompt_len,
        peaks=peaks_mod.peaks(devices[0].device_kind), tracer=tracer,
        trace=None)
    lag = np.asarray(clock["lag_ms"])
    if tracer is not None:
        log(f"tracer: started and stopped in {tracer.cost_s:.3f} s of the "
            f"window")
    log(f"window: {t_end - t_start:.3f} s, {len(executor.calls)} executor "
        f"calls, {len(sched.groups)} groups admitted, backend compiles "
        f"inside the window: {in_window}, jaxpr traces: {traced_in_window}")
    if lag.size:
        log(f"event clock behind host clock at round starts (ms): p50 "
            f"{np.percentile(lag, 50):.3f} p95 {np.percentile(lag, 95):.3f}"
            f" max {lag.max():.3f} min {lag.min():.3f}; pacing waits "
            f"{len(clock['pacing'])} totalling "
            f"{sum(b - a for a, b in clock['pacing']):.3f} s")
    log(f"longest host stretches (ms): {stalls(executor.calls, clock)}")
    mem = memory_peak(devices[:cell.chips])
    results = dict(sched.results)
    kv = correct.program_kv(cell.arch, executor.state, runs,
                            window.live_groups(sched.trace), coding.workers)
    executor.state = None
    del sched
    gc.collect()

    readings = correct.compare(cell.arch, dims, coding, params,
                               executor.calls, served, runs, results, kv,
                               max_len(cell.traffic), seed_of["sample"])
    ok, checks = correct.verdict(
        readings["program"][reference.REFERENCE],
        readings["result_mismatches"], cell.cell["limits"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": bool(ok),
           "attempted": len(served), "failed": 0}
    if trace:
        ctx.trace = reduce_trace(ctx)
        metrics = per_layer(cell, ctx)
        if ctx.trace is not None:
            device["busy_s"], device["window_s"] = device_busy(ctx.trace)
            out["breakdown"] = breakdown(ctx.trace)
    else:
        e2e = end_to_end(ctx)
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end if e2e.get(m.name) is not None}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    return out


def per_layer(cell: spec.Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m.name)(ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def parse(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description="chip benchmark: one run of "
                                             "one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None, t_process: Optional[float] = None
         ) -> None:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(spec.ROOT / "src"))
    devices = require_devices(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   t_process, log=lambda s: print(s, flush=True))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
