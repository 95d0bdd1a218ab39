"""CI gate: fail when a smoke benchmark regresses vs its baseline.

Compares the gated fields of a fresh ``--smoke --json`` run of a
figure benchmark (``fig_adaptive_redundancy``, ``fig_mesh_serving``,
``fig_scheme_faceoff``) against the checked-in baseline JSON and exits
non-zero if any gated metric exceeds ``--max-ratio`` times its baseline
value, or a floor metric falls more than ``--max-drop`` below it.  Only
keys present in BOTH documents are compared, so adding a new sweep cell
never breaks the gate; removing one prints a warning (a silently
vanished measurement would otherwise read as "no regression").

  python scripts/check_bench_regression.py \\
      benchmarks/results/FIG_scheme_faceoff.json \\
      benchmarks/baselines/fig_scheme_faceoff_smoke_baseline.json
"""

from __future__ import annotations

import argparse
import json
import sys

# Fields gated per cell as CEILINGS: the event-clock serving tail of the
# adaptive-redundancy trajectory (``p99_ms`` is simulated time off fixed
# seeds, so it is exactly reproducible — a drift there is a real
# scheduler change, not CI noise) and the mesh benchmark's compiled-HLO
# collective bytes.
_GATED = ("p99_ms", "gathered_bytes")

# Quality fields gated as FLOORS per cell (higher is better): the
# scheme-faceoff agreement runs on an exact-seeded event clock, so it
# only moves when the coding math does — a drop past --max-drop is a
# decode/locator regression, never box noise.
_GATED_FLOOR = ("agreement",)


def _cells(doc):
    # fig_mesh_serving --json: per-gather-mode cells whose
    # ``gathered_bytes`` come from compiled-HLO collective accounting —
    # deterministic, so CI gates them with a tight --max-ratio (a jump
    # means the survivor-only gather silently widened, not noise)
    for key, cell in (doc.get("mesh") or {}).items():
        yield f"mesh.{key}", cell
    # fig_adaptive_redundancy --json: one cell per serving policy
    for key, cell in (doc.get("policies") or {}).items():
        yield f"policies.{key}", cell
    # fig_scheme_faceoff --json: one cell per (facet, scheme); gated on
    # the agreement FLOOR rather than a latency ratio
    for key, cell in (doc.get("schemes") or {}).items():
        yield f"schemes.{key}", cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("current", help="fresh --smoke --json output")
    ap.add_argument("baseline", help="checked-in baseline JSON")
    ap.add_argument("--max-ratio", type=float, default=2.0,
                    help="fail when current > ratio * baseline")
    ap.add_argument("--max-drop", type=float, default=0.03,
                    help="fail when a floor metric (agreement) falls "
                         "more than this below baseline")
    args = ap.parse_args(argv)

    with open(args.current) as fh:
        current = json.load(fh)
    with open(args.baseline) as fh:
        baseline = json.load(fh)

    cur = dict(_cells(current))
    base = dict(_cells(baseline))
    failures, compared = [], 0
    for key, bcell in base.items():
        ccell = cur.get(key)
        if ccell is None:
            print(f"warning: baseline cell {key!r} missing from current "
                  "run (sweep shrank?)", file=sys.stderr)
            continue
        for field in _GATED:
            if field not in bcell or field not in ccell:
                continue
            compared += 1
            ratio = ccell[field] / max(bcell[field], 1e-9)
            unit = field.rsplit("_", 1)[-1]   # "us" / "ms" from the name
            line = (f"{key}.{field}: {ccell[field]:.1f}{unit} vs baseline "
                    f"{bcell[field]:.1f}{unit} ({ratio:.2f}x)")
            if ratio > args.max_ratio:
                failures.append(line)
                print("REGRESSION " + line)
            else:
                print("ok         " + line)
        for field in _GATED_FLOOR:
            if field not in bcell or field not in ccell:
                continue
            compared += 1
            drop = bcell[field] - ccell[field]
            line = (f"{key}.{field}: {ccell[field]:.4f} vs baseline "
                    f"{bcell[field]:.4f} (drop {drop:+.4f})")
            if drop > args.max_drop:
                failures.append(line)
                print("REGRESSION " + line)
            else:
                print("ok         " + line)
    if not compared:
        print("error: no comparable metrics between current and baseline",
              file=sys.stderr)
        return 2
    if failures:
        print(f"\n{len(failures)} metric(s) regressed (>{args.max_ratio}x "
              f"ratio or >{args.max_drop} floor drop)", file=sys.stderr)
        return 1
    print(f"\nall {compared} metrics within {args.max_ratio}x / "
          f"-{args.max_drop} of baseline")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
