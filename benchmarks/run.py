"""Benchmark aggregator: one section per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--with-dryrun]

Sections:
  1. Table I  — instrumentation overhead (hyperfine protocol)
  2. Fig 2    — system-vs-user breakdown
  3. SDFG     — IR extraction + backend assignment across all 10 archs
  4. Kernels  — hot-spot micro-benches + TPU roofline projections
  5. Roofline — 40-cell (arch × shape) table from dry-run records, if present
  6. Dispatch — static vs profile-guided backend placement (repro.dispatch)
  7. Tune     — measured design-space sweep, tuned configs vs defaults
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

OUT_DIR = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced run counts")
    ap.add_argument(
        "--with-dryrun", action="store_true",
        help="run the full 40-cell dry-run sweep (subprocess, ~30+ min) if records are missing",
    )
    args = ap.parse_args()

    from benchmarks import breakdown_fig2, kernel_bench, overhead_table1, sdfg_bench
    from repro.hw.specs import host_chip
    from repro.trace import artifact_meta

    # provenance stamp (schema/git/timestamp/chip) so `python -m repro.trace
    # diff` can compare out_all.json artifacts across PRs
    results = {"meta": artifact_meta({"fast": args.fast}, chip=host_chip())}
    print("\n########## 1. Table I: instrumentation overhead ##########")
    results["table1"] = overhead_table1.run(fast=args.fast)
    print("\n########## 2. Fig 2: system-vs-user breakdown ##########")
    results["fig2"] = breakdown_fig2.run(fast=args.fast)
    print("\n########## 3. SDFG extraction (10 architectures) ##########")
    results["sdfg"] = sdfg_bench.run(fast=args.fast)
    print("\n########## 4. Kernel micro-benches ##########")
    results["kernels"] = kernel_bench.run(fast=args.fast)

    print("\n########## 5. Roofline table (from dry-run records) ##########")
    recs_path = os.path.join(OUT_DIR, "out_dryrun_single_pod.jsonl")
    if not os.path.exists(recs_path) and args.with_dryrun:
        # the dry-run compiles for 512 placeholder CPU devices; on a TPU host
        # it must not try to take the chip this process may hold
        subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--all", "--out", recs_path],
            check=False, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    if os.path.exists(recs_path):
        from benchmarks import roofline_table

        recs = roofline_table.load(recs_path)
        print(roofline_table.render(recs))
        results["roofline_cells"] = len(recs)
    else:
        print(f"(no records at {recs_path}; run the dry-run sweep to fill this section)")

    print("\n########## 6. Dispatch: static vs profile-guided placement ##########")
    from benchmarks import dispatch_bench

    results["dispatch"] = dispatch_bench.run(fast=args.fast)

    print("\n########## 7. Tune: design-space sweep, tuned vs default ##########")
    from benchmarks import tune_bench

    results["tune"] = tune_bench.run(fast=args.fast)

    with open(os.path.join(OUT_DIR, "out_all.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("\nwrote benchmarks/out_all.json")


if __name__ == "__main__":
    main()
