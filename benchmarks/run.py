"""Benchmark runner: ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper table/figure (quick CI-sized grids by default;
pass --paper for the published experiment sizes).  Each module asserts
the paper's qualitative claims, so a green run IS the reproduction
check.
"""

from __future__ import annotations

import argparse
import json
import time
import traceback

from benchmarks import (
    admm_convergence,
    compressed_rounds,
    corollary48_threshold,
    fault_rounds,
    fig1_machines,
    fig2_fixed_n,
    fig_multiclass,
    fused_solver,
    lambda_path,
    multi_round,
    serving,
    table1_speedup,
    table2_real,
)
from benchmarks.common import bench_json_path, write_bench_json
from repro.launch.compile_cache import enable_compile_cache


BENCHES = [
    ("fig1_machines (fixed N, vary m)", fig1_machines.main),
    ("fig2_fixed_n (fixed n, N = m*n)", fig2_fixed_n.main),
    ("fig_multiclass (K-class accuracy/F1 vs m)", fig_multiclass.main),
    ("table1_speedup (wall-clock vs m)", table1_speedup.main),
    ("table2_real (heart-disease surrogate)", table2_real.main),
    ("corollary48 (machine-count threshold m*)", corollary48_threshold.main),
    ("fused_solver (scan vs fused-blocked kernel)", fused_solver.main),
    ("lambda_path (folded sweep vs sequential launches)", lambda_path.main),
    ("admm_convergence (adaptive early exit + warm starts)",
     admm_convergence.main),
    ("multi_round (refinement rounds past the one-shot m-barrier)",
     multi_round.main),
    ("compressed_rounds (top-k EF uplinks: accuracy vs bits moved)",
     compressed_rounds.main),
    ("fault_rounds (liveness-masked aggregation under faults)",
     fault_rounds.main),
    ("serving (classify hot path + streaming refit under faults)",
     serving.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="published experiment sizes (slow)")
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()
    enable_compile_cache()

    failures = []
    summary_rows = []
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"\n##### {name}")
        try:
            fn(paper=args.paper)
            summary_rows.append([name, "ok", time.time() - t0])
            print(f"##### {name}: OK ({time.time() - t0:.1f}s)")
        except Exception:
            failures.append(name)
            summary_rows.append([name, "failed", time.time() - t0])
            traceback.print_exc()
            print(f"##### {name}: FAILED")
    # per-benchmark status + wall-clock, diffable across PRs alongside
    # the per-shape BENCH_<name>.json files the benchmarks themselves
    # emit.  Merged by benchmark name so CI's separate --only
    # invocations accumulate into one summary instead of clobbering it.
    header = ["benchmark", "status", "seconds"]
    try:
        with open(bench_json_path("run_summary")) as f:
            prior = {r["benchmark"]: [r[c] for c in header]
                     for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError):
        prior = {}
    prior.update({r[0]: r for r in summary_rows})
    write_bench_json("run_summary", header,
                     [prior[name] for name, _ in BENCHES if name in prior],
                     paper=args.paper)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()
