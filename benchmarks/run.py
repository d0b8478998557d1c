"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (DESIGN.md §7) + the kernel microbench
+ the §Roofline table (from the dry-run artifacts, if present).
``--full`` runs at larger scale; default is the quick CI profile.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from repro import runtime

from benchmarks import (
    bench_accuracy_distribution,
    bench_buffer_size,
    bench_build,
    bench_construction,
    bench_kernels,
    bench_planner,
    bench_serving,
    bench_sketch_ablation,
    bench_space_accuracy,
    bench_threshold,
    bench_time_accuracy,
    bench_uniform_exact,
    bench_zipf_sweep,
)

SUITES = [
    ("fig5_buffer_size", bench_buffer_size),
    ("fig6_sketch_ablation", bench_sketch_ablation),
    ("fig10_13_space_accuracy", bench_space_accuracy),
    ("fig14_accuracy_distribution", bench_accuracy_distribution),
    ("fig15_threshold", bench_threshold),
    ("fig16_zipf_sweep", bench_zipf_sweep),
    ("fig17_time_accuracy", bench_time_accuracy),
    ("fig18_t3_construction", bench_construction),
    ("fig19_uniform_exact", bench_uniform_exact),
    ("kernel_microbench", bench_kernels),
    ("planner", bench_planner),
    ("build", bench_build),
    ("serving", bench_serving),
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# suite name -> repo-root JSON artifact written under --json.
JSON_ARTIFACTS = {
    "planner": os.path.join(REPO_ROOT, "BENCH_PLANNER.json"),
    "build": os.path.join(REPO_ROOT, "BENCH_BUILD.json"),
    "serving": os.path.join(REPO_ROOT, "BENCH_SERVING.json"),
}


def _print_rows(rows, limit=100):
    if not rows:
        print("  (no rows)")
        return
    cols = list(rows[0].keys())
    print("  " + " | ".join(f"{c}" for c in cols))
    for r in rows[:limit]:
        print("  " + " | ".join(str(r.get(c, "")) for c in cols))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="substring filter over suite names")
    ap.add_argument("--suite", default="",
                    help="run exactly one suite by name (e.g. planner)")
    ap.add_argument("--json", action="store_true",
                    help="also write machine-readable artifacts at the "
                         "repo root (e.g. BENCH_PLANNER.json)")
    ap.add_argument("--backend", default="jnp",
                    choices=("numpy", "jnp", "pallas"),
                    help="scoring backend for the planner suite")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit the cost-model query-path constants from "
                         "measured QPS and embed them in the planner "
                         "JSON artifact (requires --json)")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail the planner suite if pruned-path QPS "
                         "regresses >20%% below the committed "
                         "BENCH_PLANNER.json (dense-ratio normalized)")
    args = ap.parse_args()
    runtime.use_compile_cache()

    if args.suite and args.suite not in {n for n, _ in SUITES}:
        # A typo here must not green-light CI with zero suites run.
        ap.error(f"unknown suite {args.suite!r}; "
                 f"available: {[n for n, _ in SUITES]}")

    failures = 0
    for name, mod in SUITES:
        if args.suite and name != args.suite:
            continue
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"\n=== {name} ===")
        try:
            kwargs = {}
            if args.json and name in JSON_ARTIFACTS:
                kwargs["json_out"] = JSON_ARTIFACTS[name]
            if name == "planner":
                kwargs["backend"] = args.backend
                if args.calibrate:
                    kwargs["calibrate"] = True
                if args.check_baseline:
                    kwargs["baseline"] = JSON_ARTIFACTS["planner"]
            if name == "build":
                kwargs["backend"] = args.backend
                if args.check_baseline:
                    kwargs["baseline"] = JSON_ARTIFACTS["build"]
            if name == "serving":
                kwargs["backend"] = args.backend
                if args.check_baseline:
                    kwargs["baseline"] = JSON_ARTIFACTS["serving"]
            rows = mod.run(quick=not args.full, **kwargs)
            _print_rows(rows)
            print(f"  [{time.time()-t0:.1f}s] → reports/bench/{name}.csv")
            if "json_out" in kwargs:
                print(f"  → {kwargs['json_out']}")
        except Exception:
            failures += 1
            print(f"  FAILED after {time.time()-t0:.1f}s")
            traceback.print_exc()

    if args.suite:
        # Targeted smoke run (CI): skip the roofline epilogue.
        print(f"\n{'SUITE OK' if not failures else f'{failures} FAILURES'}")
        sys.exit(1 if failures else 0)

    print("\n=== roofline (from dry-run artifacts) ===")
    try:
        import os

        from benchmarks import roofline
        dd = ("reports/dryrun_v2" if os.path.isdir("reports/dryrun_v2")
              else "reports/dryrun")
        print(f"  source: {dd} (optimized defaults; baseline snapshot in "
              "reports/roofline_baseline.csv)")
        rows = roofline.run(dryrun_dir=dd)
        if rows:
            _print_rows(rows, limit=50)
            print("  → reports/roofline.csv")
        else:
            print("  no dry-run artifacts; run: "
                  "PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes")
    except Exception:
        failures += 1
        traceback.print_exc()

    print(f"\n{'ALL BENCHMARKS OK' if not failures else f'{failures} FAILURES'}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
