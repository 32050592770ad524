"""Benchmark harness — one module per paper table/figure.

  kernel_bench     Fig.3 / Fig.9 / Fig.12 — SpMM kernel grid
  utilization      Fig.10 / Fig.11 — unit utilisation + stage breakdown
  e2e_throughput   Fig.13 / Fig.15 / Fig.16 + Table 1 — tokens/chip-s, memory
  serving_load     DESIGN.md §13 — open-loop TTFT/TPOT percentiles
  spec_decode      DESIGN.md §11 — speculative tokens/step + accept rate
  format_bench     Tiled-CSL format: compression, slot padding, bytes/nnz
  pruning_study    §6.3.1 — pruning accuracy case study (reduced scale)
  roofline (CSV)   §Roofline rows from dry-run records, when present

Prints ``name,us_per_call,derived`` CSV. ``--seed`` selects the loadgen
traffic traces (`serving.loadgen`) the serving/e2e benches replay — same
seed, byte-identical trace — so two runs at one seed are comparable.
Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--seed N] [--only MODULE]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full paper grid (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="loadgen trace seed (reproducible traffic)")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (e2e_throughput, format_bench, kernel_bench,
                            pruning_study, serving_load, spec_decode,
                            utilization)
    # seeded modules replay loadgen traffic and take the trace seed
    modules = {
        "kernel_bench": kernel_bench.run,
        "utilization": utilization.run,
        "e2e_throughput": lambda full: e2e_throughput.run(
            full=full, seed=args.seed),
        "serving_load": lambda full: serving_load.run(
            full=full, seed=args.seed),
        "spec_decode": spec_decode.run,
        "format_bench": format_bench.run,
        "pruning_study": pruning_study.run,
    }
    print("name,us_per_call,derived")
    for name, fn in modules.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            for row in fn(full=args.full):
                print(row)
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    # roofline rows (only if dry-run records exist)
    if not args.only or args.only == "roofline":
        try:
            from benchmarks import roofline_report
            recs = roofline_report.load_records()
            for row in roofline_report.csv_rows(recs):
                print(row)
        except Exception:  # noqa: BLE001 — dry-run not yet executed
            pass


if __name__ == "__main__":
    main()
