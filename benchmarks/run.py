"""Benchmark harness entry point — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``
prints ``name,us_per_call,derived`` CSV rows for every experiment.

``--smoke`` runs every entrypoint in tiny-shapes mode (sets
REPRO_BENCH_SMOKE=1 before any benchmark import) — the CI guard against
import/API drift.  ``--json PATH`` additionally collects each module's
``run()`` return value into one JSON document (uploaded as a CI
artifact).  ``--only SUBSTR`` filters modules by name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _jsonable(obj):
    """Best-effort conversion of benchmark results (numpy scalars, tuple
    keys) into JSON-serializable structures."""
    import numpy as np
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer, np.floating)):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (str, int, bool)) or obj is None:
        return obj
    if isinstance(obj, float):
        return obj
    return repr(obj)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shapes mode: every entrypoint, minimal cost")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write collected run() results as JSON")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only modules whose name contains SUBSTR")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        # must precede ANY benchmarks.* import: modules size their sweeps
        # off benchmarks.common.SMOKE at import time
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from benchmarks import (bench_coded_round, bench_kernels, fig_acc_archs,
                            fig_acc_trained_lm, fig_acc_vs_e,
                            fig_acc_vs_k, fig_acc_vs_s,
                            fig_adaptive_redundancy, fig_byzantine_serving,
                            fig_mesh_serving, fig_scheme_faceoff, fig_sigma,
                            fig_cvote_ablation, fig_systematic,
                            fig_tail_latency, roofline_table,
                            table_overhead)

    modules = [
        ("fig_acc_vs_k (paper Figs 3/5/6)", fig_acc_vs_k),
        ("fig_acc_vs_s (paper Fig 7)", fig_acc_vs_s),
        ("fig_acc_vs_e (paper Fig 9)", fig_acc_vs_e),
        ("fig_sigma (paper Fig 11)", fig_sigma),
        ("fig_acc_archs (paper Figs 8/10)", fig_acc_archs),
        ("fig_acc_trained_lm (trained-model coded serving)",
         fig_acc_trained_lm),
        ("fig_systematic (beyond-paper)", fig_systematic),
        ("fig_tail_latency (paper §1 motivation)", fig_tail_latency),
        ("fig_cvote_ablation (DESIGN §3 adaptation)", fig_cvote_ablation),
        ("fig_byzantine_serving (DESIGN §8 attack sweep)",
         fig_byzantine_serving),
        ("fig_adaptive_redundancy (DESIGN §12 closed loop)",
         fig_adaptive_redundancy),
        ("fig_mesh_serving (DESIGN §13 survivor-only gather)",
         fig_mesh_serving),
        ("fig_scheme_faceoff (paper Figs 3/5/6 + §1 overhead, one sweep)",
         fig_scheme_faceoff),
        ("table_overhead (paper §1/§4)", table_overhead),
        ("bench_coded_round (fused round hot path, perf trajectory)",
         bench_coded_round),
        ("bench_kernels", bench_kernels),
        ("roofline_table (deliverable g)", roofline_table),
    ]
    if args.only:
        modules = [(t, m) for t, m in modules
                   if args.only in m.__name__.split(".")[-1]]
    print("name,us_per_call,derived")
    failures = 0
    collected = {}
    for title, mod in modules:
        print(f"# --- {title}", file=sys.stderr)
        try:
            collected[mod.__name__.split(".")[-1]] = mod.run()
        except Exception as exc:  # keep the harness running
            failures += 1
            print(f"{mod.__name__},0.0,ERROR={exc!r}")
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump({"smoke": args.smoke,
                       "results": _jsonable(collected)}, fh, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
