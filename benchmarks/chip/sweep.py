"""Find an open-loop cell's knee: the highest offered rate it sustains.

  python3 benchmarks/chip/sweep.py --workload <name> --rates 4,8,12 \\
      --seconds 30 --seed 1

In one process, one window per rate over the cell's traffic mix with
``rate_rps`` replaced.  A rate is sustained when the queue does not grow
through the window: requests still waiting for admission at its end stay
few, and time to first token in the window's last third is no worse than
in its first third.  The cell's ``rate_rps`` is then set to about four
fifths of the knee, once; the benchmark's own runs never search.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import spec  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import window  # noqa: E402


def sweep(cell: spec.Cell, rates, seconds: float, seed: int,
          log=print) -> list:
    seed_of = harness.seeds(seed)
    dims, coding, _, executor, scheduler = harness.build(cell, seed_of)
    harness.warm_up(executor, scheduler, int(cell.traffic["prompt_len"]),
                    coding.k, dims.vocab)
    rows = []
    for rate in rates:
        executor.calls.clear()
        executor.state = None
        requests = traffic_mod.generate(cell.traffic, rate, seconds,
                                        dims.vocab, seed_of["traffic"])
        sched, _, t0, t1 = harness.run_window(executor, scheduler, requests,
                                              seconds)
        served, _ = window.rebuild(sched.trace, sched.groups, executor.calls,
                                   coding.k, t0)
        due = {i: t0 + a / 1e3 for i, a in enumerate(requests.arrival_ms)}
        ttft = window.ttfts_ms(due, served, t1)
        in_window = [u for u, d in due.items() if d <= t1]
        third = len(ttft) // 3
        row = {
            "rate_rps": rate, "due": len(in_window),
            "admitted": len(served),
            "waiting_at_end": len(in_window) - len(served),
            "tokens_per_s": window.tokens_served(served) / (t1 - t0),
            "ttft_ms_p50": window.percentile(ttft, 50),
            "ttft_ms_p95": window.percentile(ttft, 95),
            "ttft_ms_p95_first_third": window.percentile(ttft[:third], 95),
            "ttft_ms_p95_last_third": window.percentile(ttft[-third:], 95),
            "itl_ms_p95": window.percentile(window.itls_ms(served), 95)}
        log(json.dumps(row))
        rows.append(row)
        del sched
        gc.collect()
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--pool-groups", type=int, default=None,
                    help="try another pool size than the cell file's")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if args.pool_groups:
        cell = dataclasses.replace(cell, cell=dict(
            cell.cell, pool_groups=args.pool_groups))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(spec.ROOT / "src"))
    harness.require_devices(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sweep(cell, [float(r) for r in args.rates.split(",")],
          args.seconds, args.seed, log=lambda s: print(s, flush=True))


if __name__ == "__main__":
    main()
