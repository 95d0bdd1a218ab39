"""Readings behind a cell's correctness limits, and its control.

  python3 benchmarks/chip/calibrate.py --workload <name> \\
      --seeds 1,2,3 --seconds 30

In one process, for each seed: fresh weights, traffic and pool through
the timed path for one window, then the comparison of ``correct.py``
over the same sampled requests, for two sides: the served ids (the
program), and the ids the reference stored in bfloat16 ranks first (the
control, the step below the float32 the configuration states).  Each
side is read against the reference in the numerics the configuration
states and, for the look behind them, in the other float32 numerics of
``reference.NUMERICS``, and each side's readings go through
``correct.verdict`` with the cell's limits: the program has to come out
correct, the control not.  The limits in ``cells/<name>.json`` lie
between the program's largest and the control's smallest readings.  The
benchmark's own runs do not run this.  With ``--fault`` a fault of
``faults.py`` is planted under the executor first, and the program's
readings are the fault's.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import correct  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import weights  # noqa: E402
import window  # noqa: E402

CONTROL = "bfloat16"
REFERENCES = tuple(n for n in reference.NUMERICS if n.startswith("float32"))


def readings(cell: spec.Cell, seeds, seconds: float, log=print) -> list:
    """One dict of readings per seed, program and control side by side."""
    first = harness.seeds(seeds[0])
    dims, coding, params, executor, _ = harness.build(cell, first)
    prompt_len = int(cell.traffic["prompt_len"])
    out = []
    for seed in seeds:
        seed_of = harness.seeds(seed)
        if seed != seeds[0]:
            executor.params = params = weights.make(cell.arch, dims,
                                                    seed_of["weights"])
        scheduler = harness.build_scheduler(cell, executor, coding, seed_of)
        harness.warm_up(executor, scheduler, prompt_len, coding.k,
                        dims.vocab)
        requests = traffic_mod.generate(
            cell.traffic, cell.cell.get("rate_rps"), seconds, dims.vocab,
            seed_of["traffic"])
        sched, _, t_start, _ = harness.run_window(executor, scheduler,
                                                  requests, seconds)
        served, runs = window.rebuild(sched.trace, sched.groups,
                                      executor.calls, coding.k, t_start)
        results = dict(sched.results)
        kv = correct.program_kv(cell.arch, executor.state, runs,
                                window.live_groups(sched.trace),
                                coding.workers)
        executor.state = None
        del sched
        gc.collect()
        r = correct.compare(cell.arch, dims, coding, params,
                            executor.calls, served, runs, results, kv,
                            harness.max_len(cell.traffic),
                            seed_of["sample"], references=REFERENCES,
                            controls=(CONTROL,))
        limits = cell.cell["limits"]
        ref = reference.REFERENCE
        r["program_correct"] = correct.verdict(
            r["program"][ref], r["result_mismatches"], limits)[0]
        r["control_correct"] = correct.verdict(r[CONTROL][ref], 0,
                                               limits)[0]
        r["seed"] = seed
        log(json.dumps(r))
        out.append(r)
    return out


def main(argv=None) -> None:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None,
                    help="plant this fault under the executor: the "
                         "program's readings are then the fault's")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(spec.ROOT / "src"))
    harness.require_devices(cell.chips)
    if args.fault:
        faults.plant(args.fault)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rs = readings(cell, seeds, args.seconds,
                  log=lambda s: print(s, flush=True))
    summary = {"workload": args.workload, "fault": args.fault,
               "seeds": len(rs),
               "seconds": time.perf_counter() - t0,
               "program_correct": sum(r["program_correct"] for r in rs),
               "control_correct": sum(r["control_correct"] for r in rs)}
    for ref in REFERENCES:
        for k in ("max_logit_gap", "mean_logit_gap", "not_best_share",
                  "kv_rel_err"):
            summary[f"{ref}.program_{k}_max"] = max(
                r["program"][ref][k] for r in rs)
            summary[f"{ref}.control_{k}_min"] = min(
                r[CONTROL][ref][k] for r in rs)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
