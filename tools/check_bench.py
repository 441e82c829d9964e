#!/usr/bin/env python3
"""Benchmark regression gate: diff a fresh BENCH_compiler.json vs baseline.

``benchmarks/run.py`` writes machine-readable records (cycles, energy,
exactness, deployed accuracy/AEE) for every tracked ablation; this tool
compares a freshly generated file against the committed
``benchmarks/baseline.json`` and fails loudly when a record regresses:

  * a record present in the baseline disappears;
  * an exactness flag that was True turns False (bit-exactness is a hard
    contract, no tolerance);
  * ``cycles`` / ``energy_uj`` grow beyond ``--tol`` (relative);
  * the deployed quality metric regresses beyond ``--tol-metric``
    (absolute) — ``accuracy`` falling or ``aee`` rising;
  * the measured/roofline ratio ``wall_us / bound_us`` grows beyond
    ``--tol-roofline`` (relative) — only for records whose BASELINE
    carries both fields.  Raw ``wall_us`` stays ungated (CI runners are
    not comparable machines); the analytic bound from
    ``roofline.analysis.PerfModel`` normalizes shape/sparsity/tiling out
    of the wall clock, so the ratio moves only when the implementation
    gets slower relative to what its dataflow should cost.  The default
    tolerance is deliberately loose (3.0 = 4x the committed ratio):
    interpret-mode wall clock jitters across runners, and the gate exists
    to catch order-of-magnitude schedule regressions (a dropped
    block-skip, a retraced jit).

Improvements (fewer cycles, less energy, better metric) always pass, with
a note suggesting a baseline refresh so the gate tightens over time.

Usage:
    PYTHONPATH=src python benchmarks/run.py --smoke --out BENCH_compiler.json
    python tools/check_bench.py BENCH_compiler.json

Refreshing the baseline after an intentional change:
    PYTHONPATH=src python benchmarks/run.py --smoke --out benchmarks/baseline.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_BASELINE = REPO / "benchmarks" / "baseline.json"

REFRESH_HINT = (
    "If this change is intentional, refresh the committed baseline with:\n"
    "    PYTHONPATH=src python benchmarks/run.py --smoke "
    "--out benchmarks/baseline.json\n"
    "and commit the result."
)

# Numeric fields under the relative ``--tol`` gate; True = lower is better.
COST_FIELDS = {"cycles": True, "energy_uj": True}
# Quality metrics under the absolute ``--tol-metric`` gate.
HIGHER_BETTER_METRICS = {"accuracy"}
LOWER_BETTER_METRICS = {"aee"}


def _load(path: pathlib.Path) -> dict:
    payload = json.loads(path.read_text())
    records = payload.get("results", [])
    if not records:
        raise SystemExit(f"ERROR: {path} contains no benchmark records")
    return {r["name"]: r for r in records}


def _check_record(base: dict, fresh: dict, tol: float, tol_metric: float,
                  tol_roofline: float = 3.0):
    """Yield failure strings for one record pair."""
    name = base["name"]
    # Roofline-ratio gate: applies only when the BASELINE committed both a
    # measured wall time and a predicted bound (records without bound_us
    # keep the long-standing contract that wall_us alone is ignored).
    if "wall_us" in base and "bound_us" in base:
        if "wall_us" in fresh and "bound_us" in fresh:
            base_ratio = base["wall_us"] / max(base["bound_us"], 1e-12)
            got_ratio = fresh["wall_us"] / max(fresh["bound_us"], 1e-12)
            limit = base_ratio * (1.0 + tol_roofline)
            if got_ratio > limit:
                yield (
                    f"{name}: wall/roofline ratio regressed "
                    f"{base_ratio:.1f} -> {got_ratio:.1f} "
                    f"(+{(got_ratio / max(base_ratio, 1e-12) - 1) * 100:.0f}%, "
                    f"tolerance {tol_roofline * 100:.0f}%) — measured "
                    f"{fresh['wall_us']:.0f}us vs predicted bound "
                    f"{fresh['bound_us']:.1f}us"
                )
        # A missing wall_us/bound_us falls through to the field-disappeared
        # check below, which reports it.
    for field, value in base.items():
        if field not in fresh:
            yield f"{name}: field '{field}' disappeared from the fresh run"
            continue
        got = fresh[field]
        if field in COST_FIELDS:
            limit = value * (1.0 + tol)
            if got > limit:
                yield (
                    f"{name}: {field} regressed {value} -> {got} "
                    f"(+{(got / max(value, 1e-12) - 1) * 100:.1f}%, "
                    f"tolerance {tol * 100:.0f}%)"
                )
        elif isinstance(value, bool):
            if value and not got:
                yield f"{name}: {field} was True in the baseline, now {got}"
        elif field == "metric_value":
            metric = base.get("metric", "")
            if metric in HIGHER_BETTER_METRICS and got < value - tol_metric:
                yield (
                    f"{name}: {metric} regressed {value:.4f} -> {got:.4f} "
                    f"(tolerance {tol_metric})"
                )
            if metric in LOWER_BETTER_METRICS and got > value + tol_metric:
                yield (
                    f"{name}: {metric} regressed {value:.4f} -> {got:.4f} "
                    f"(tolerance {tol_metric})"
                )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", help="freshly generated BENCH_compiler.json")
    ap.add_argument(
        "--baseline",
        default=str(DEFAULT_BASELINE),
        help="committed baseline JSON (default: benchmarks/baseline.json)",
    )
    ap.add_argument(
        "--tol",
        type=float,
        default=0.25,
        help="relative tolerance for cycles/energy regressions (default 0.25)",
    )
    ap.add_argument(
        "--tol-metric",
        type=float,
        default=0.05,
        help="absolute tolerance for accuracy/AEE regressions (default 0.05)",
    )
    ap.add_argument(
        "--tol-roofline",
        type=float,
        default=3.0,
        help="relative tolerance for the wall_us/bound_us roofline ratio "
        "(default 3.0; applies only to records whose baseline has both "
        "fields)",
    )
    ap.add_argument(
        "--subset",
        action="store_true",
        help="the fresh file covers only part of the baseline (e.g. a "
        "--qat-sweep run): gate the overlapping records instead of "
        "failing on the missing ones",
    )
    args = ap.parse_args(argv)

    base = _load(pathlib.Path(args.baseline))
    fresh = _load(pathlib.Path(args.fresh))
    if args.subset:
        base = {k: v for k, v in base.items() if k in fresh}
        if not base:
            raise SystemExit(
                "ERROR: --subset run shares no record names with the baseline"
            )

    failures: list = []
    improvements = 0
    for name, record in sorted(base.items()):
        if name not in fresh:
            failures.append(
                f"{name}: record missing from the fresh run (ablation "
                "removed or renamed?)"
            )
            continue
        errs = list(_check_record(record, fresh[name], args.tol,
                                  args.tol_metric, args.tol_roofline))
        failures.extend(errs)
        if not errs:
            for field, lower_better in COST_FIELDS.items():
                got, ref = fresh[name].get(field), record.get(field)
                if got is not None and ref is not None and got < ref:
                    improvements += 1
                    break
            print(f"PASS {name}")
    new = sorted(set(fresh) - set(base))
    if new:
        print(f"note: {len(new)} new record(s) not in the baseline: {new}")
    if improvements:
        print(
            f"note: {improvements} record(s) improved on the baseline — "
            "consider refreshing it to lock in the gains"
        )

    if failures:
        print(f"\nFAILED: {len(failures)} regression(s) vs {args.baseline}:")
        for f in failures:
            print(f"  - {f}")
        print()
        print(REFRESH_HINT)
        return 1
    print(f"OK: {len(base)} record(s) within tolerance of {args.baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
