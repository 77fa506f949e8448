"""Benchmark aggregator: one module per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--only fig3,table4] [--quick]

`--quick` (the CI smoke lane) sets BENCH_QUICK=1 so modules shrink their
grids; `--full` selects the paper-scale grid.  Results land in
results/bench/*.json; a summary prints per module.  Each module also
emits a machine-readable perf-trajectory artifact
``results/bench/BENCH_<name>.json`` (schema ``repro-bench/1``, flat
``"<dataset>.<metric>": float`` map — see `common.write_result`) that CI
uploads alongside the raw results and `check_regression.py` accepts
directly.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

MODULES = [
    "table4_storage",
    "table_kernels",
    "bench_serving",
    "bench_offline",
    "bench_train",
    "bench_distributed",
    "bench_streaming",
    "bench_lifecycle",
    "bench_planner",
    "bench_faults",
    "bench_serving_load",
    "fig3_macro",
    "fig4_lesion",
    "fig5_feature_importance",
    "table5_picker_latency",
    "table3_speedup",
    "fig7_selectivity",
    "fig9_generalization",
    "fig10_alpha",
    "fig12_estimators",
    "table6_clustering",
    "fig6_layouts",
    "fig8_partitions",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true", help="paper-scale grid")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke lane: reduced grids (BENCH_QUICK=1)")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    if args.full:
        os.environ["BENCH_FULL"] = "1"
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    if args.only:
        unknown = sorted(set(args.only.split(",")) - set(MODULES))
        if unknown:  # a typo'd --only must not report 0/0 OK in CI
            ap.error(f"unknown benchmark module(s): {', '.join(unknown)}")
    todo = [m for m in MODULES if not args.only or m in args.only.split(",")]
    from repro.backends import use_checkout_compile_cache

    use_checkout_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    entries: list[tuple[str, str, float]] = []
    t_all = time.time()
    for name in todo:
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        t0 = time.time()
        try:
            import jax

            jax.clear_caches()  # bound the jit cache across modules
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            entries.append((name, "OK", time.time() - t0))
            print(f"--- {name} done in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            entries.append((name, "FAIL", time.time() - t0))

    # self-describing summary: CI artifacts must show what ran, on which
    # backend, and what each entry cost — not just an aggregate OK count
    from repro.backends import default_backend
    import jax

    failures = [name for name, status, _ in entries if status == "FAIL"]
    print(f"\neval backend: {default_backend()} (platform: {jax.default_backend()}; "
          f"override via REPRO_EVAL_BACKEND)")
    for name, status, secs in entries:
        print(f"  {name:<28} {status:<5} {secs:7.1f}s")
    arts = sorted(
        f for f in os.listdir("results/bench")
        if f.startswith("BENCH_") and f.endswith(".json")
    ) if os.path.isdir("results/bench") else []
    if arts:
        print(f"perf-trajectory artifacts (results/bench/): {', '.join(arts)}")
    print(f"{len(todo) - len(failures)}/{len(todo)} benchmarks OK "
          f"in {time.time() - t_all:.0f}s")
    if failures:
        print("FAILED:", ", ".join(failures))
        sys.exit(1)


if __name__ == "__main__":
    main()
