"""Benchmark harness driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig10,table1]
  PYTHONPATH=src python -m benchmarks.run --only e2e --gate
  PYTHONPATH=src python -m benchmarks.run --gate            # gate only
  PYTHONPATH=src python -m benchmarks.run --seed-baseline   # new baseline

Prints ``name,us_per_call,derived`` CSV rows and writes
artifacts/bench/results.json.

``--gate`` is the perf-trajectory regression gate: every
``BENCH_*.json`` in the baseline directory (``benchmarks/trajectory/``
committed in-repo, overridable via ``REPRO_BENCH_BASELINE`` or
``--baseline``) is compared row-by-row against the freshly produced
file in ``artifacts/bench/``.  Only machine-relative *ratio* columns
(`GATE_RATIO_KEYS`) are gated — absolute microseconds differ across CI
runners, but fused/staged and tuned/default ratios are comparisons of
two candidates timed counterbalanced on the same machine, so a drop
beyond the noise margin is a real regression.  ``--seed-baseline``
copies the current artifacts into the baseline directory (run after an
intentional perf change, commit the result).
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import time
import traceback

MODULES = [
    ("fig1", "benchmarks.bench_fig1_breakdown"),
    ("obs1", "benchmarks.bench_obs1_exact_match"),
    ("obs2", "benchmarks.bench_obs2_locations"),
    ("table1", "benchmarks.bench_table1_scores"),
    ("fig8", "benchmarks.bench_fig8_capacity"),
    ("fig9", "benchmarks.bench_fig9_nmsl_roofline"),
    ("fig10", "benchmarks.bench_fig10_residuals"),
    ("fig12", "benchmarks.bench_fig12_error_rate"),
    ("fig13", "benchmarks.bench_fig13_threshold"),
    ("table3", "benchmarks.bench_table3_modules"),
    ("table5", "benchmarks.bench_table5_end2end"),
    ("table7", "benchmarks.bench_table7_accuracy"),
    ("longread", "benchmarks.bench_longread"),
    ("kernels", "benchmarks.bench_kernels"),
    ("cand_align", "benchmarks.bench_candidate_align"),
    ("pair_frontend", "benchmarks.bench_pair_frontend"),
    ("residual_dp", "benchmarks.bench_residual_dp"),
    ("serve", "benchmarks.bench_serve"),
    ("e2e", "benchmarks.bench_e2e"),
    ("coldstart", "benchmarks.bench_coldstart"),
]

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")
TRAJECTORY = os.path.join(os.path.dirname(__file__), "trajectory")

# The ratio-valued derived columns the gate compares.  Each is a
# same-machine A/B comparison (counterbalanced reps), so it transfers
# across runners; absolute us_per_call does not and is never gated.
GATE_RATIO_KEYS = (
    "speedup",
    "frontdoor_vs_raw",
    "tuned_vs_default",
    "tuned_vs_staged",
    "load_vs_build",
)
# Noise margin: a ratio may drop to (1 - margin) of the baseline before
# the gate fails.  CPU CI ratios for these benches wobble ~10%; 25%
# keeps flakes out while still catching a real "fused path fell back to
# staged" or "tuner picked a loser" regression (those move 2x+).
GATE_MARGIN = 0.25


def baseline_dir(explicit: str | None = None) -> str:
    return (explicit or os.environ.get("REPRO_BENCH_BASELINE")
            or TRAJECTORY)


def gate(explicit_baseline: str | None = None,
         margin: float = GATE_MARGIN) -> tuple[list[str], int]:
    """Compare artifacts/bench/BENCH_*.json against the baseline point.

    Returns (failures, n_ratios_checked).  Every BENCH file present in
    the baseline must exist in artifacts with every baseline row still
    present and every gated ratio >= baseline*(1-margin).
    """
    base = baseline_dir(explicit_baseline)
    failures: list[str] = []
    checked = 0
    base_files = sorted(glob.glob(os.path.join(base, "BENCH_*.json")))
    if not base_files:
        return [f"no BENCH_*.json baseline in {base} "
                f"(run --seed-baseline first)"], 0
    for bpath in base_files:
        name = os.path.basename(bpath)
        cpath = os.path.join(ART, name)
        if not os.path.exists(cpath):
            failures.append(f"{name}: no current file in {ART} "
                            f"(bench did not run?)")
            continue
        with open(bpath) as f:
            old = json.load(f)
        with open(cpath) as f:
            new = json.load(f)
        new_rows = {r["name"]: r for r in new.get("rows", [])}
        for orow in old.get("rows", []):
            nrow = new_rows.get(orow["name"])
            if nrow is None:
                failures.append(f"{name}: row {orow['name']!r} "
                                f"disappeared")
                continue
            for key in GATE_RATIO_KEYS:
                if key not in orow.get("derived", {}):
                    continue
                if key not in nrow.get("derived", {}):
                    failures.append(
                        f"{name}: {orow['name']}.{key} missing from "
                        f"current run")
                    continue
                ov = float(orow["derived"][key])
                nv = float(nrow["derived"][key])
                checked += 1
                if nv < ov * (1.0 - margin):
                    failures.append(
                        f"{name}: {orow['name']}.{key} regressed "
                        f"{ov:.3f} -> {nv:.3f} "
                        f"(floor {ov * (1 - margin):.3f})")
    return failures, checked


def seed_baseline(explicit_baseline: str | None = None) -> list[str]:
    """Copy the current artifacts into the trajectory baseline dir."""
    base = baseline_dir(explicit_baseline)
    os.makedirs(base, exist_ok=True)
    copied = []
    for cpath in sorted(glob.glob(os.path.join(ART, "BENCH_*.json"))):
        shutil.copy2(cpath, os.path.join(base, os.path.basename(cpath)))
        copied.append(os.path.basename(cpath))
    return copied


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys")
    ap.add_argument("--gate", action="store_true",
                    help="compare artifacts/bench against the committed "
                         "trajectory baseline; alone = gate only (no "
                         "benches run), with --only = run then gate")
    ap.add_argument("--gate-margin", type=float, default=GATE_MARGIN,
                    help="allowed fractional ratio drop before failing")
    ap.add_argument("--baseline", default=None,
                    help="baseline dir (default benchmarks/trajectory, "
                         "env REPRO_BENCH_BASELINE overrides)")
    ap.add_argument("--seed-baseline", action="store_true",
                    help="copy current BENCH_*.json artifacts into the "
                         "baseline dir (after running any --only set)")
    args = ap.parse_args()
    keys = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    failures = []
    # Benches run when a module set is named, or on a plain invocation;
    # bare --gate / --seed-baseline operate on existing artifacts only.
    run_benches = (args.only is not None
                   or not (args.gate or args.seed_baseline))
    if run_benches:
        from benchmarks.common import print_rows
        all_rows = []
        print("name,us_per_call,derived", flush=True)
        for key, modname in MODULES:
            if keys and key not in keys:
                continue
            t0 = time.time()
            try:
                mod = importlib.import_module(modname)
                rows = mod.run()
                print_rows(rows)
                all_rows.extend(rows)
                print(f"# {key}: {len(rows)} rows in "
                      f"{time.time()-t0:.1f}s", flush=True)
            except Exception as e:  # noqa: BLE001 — report all, fail at end
                traceback.print_exc()
                failures.append((key, repr(e)))
                print(f"# {key}: FAILED {e!r}", flush=True)

        os.makedirs(ART, exist_ok=True)
        with open(os.path.join(ART, "results.json"), "w") as f:
            json.dump({"rows": all_rows, "failures": failures}, f,
                      indent=1, default=str)

    if args.seed_baseline:
        copied = seed_baseline(args.baseline)
        print(f"# seeded baseline {baseline_dir(args.baseline)}: "
              f"{copied}", flush=True)

    if args.gate:
        gate_failures, checked = gate(args.baseline, args.gate_margin)
        if gate_failures:
            for gf in gate_failures:
                print(f"# GATE FAIL: {gf}", flush=True)
            failures.extend(("gate", gf) for gf in gate_failures)
        else:
            print(f"# gate OK: {checked} ratios within "
                  f"{args.gate_margin:.0%} of baseline", flush=True)

    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
