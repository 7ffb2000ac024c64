"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Select subsets with
``python -m benchmarks.run table5 fig13 ...``; no args runs everything.
``--smoke`` routes uniformly to every selected suite's CI quick-lane
smoke check (``common.smoke_requested`` is the single interpretation of
the flag) — suites without one are skipped with a comment line.
"""
from __future__ import annotations

import sys
import time

from . import (adaptive_order, comparative, construction, effect_of_n,
               filter_throughput, granularity, kernel_bench, linestring,
               mbr_join, partitioning, pipeline_e2e, refinement, scaleout,
               selection, service_throughput, size_variance, space,
               within_join)
from .common import smoke_requested

SUITES = {
    "table4_space": space,
    "table5_effect_of_n": effect_of_n,
    "table8_partitioning": partitioning,
    "table10_granularity": granularity,
    "table11_construction": construction,
    "table13_size_variance": size_variance,
    "table15_selection": selection,
    "table16_within": within_join,
    "table17_linestring": linestring,
    "fig13_comparative": comparative,
    # emits BENCH_planner.json: adaptive planner vs the static config
    # sweep; also carries the Table-7 join-order rows (paper §7.2.2)
    "planner_table7_join_order": adaptive_order,
    "kernels": kernel_bench,
    # emits BENCH_filter.json: sequential vs batched verdict throughput
    "filter_throughput": filter_throughput,
    # emits BENCH_refine.json: sequential vs batched refinement throughput
    "refinement": refinement,
    # emits BENCH_mbr.json: sequential vs batched candidate generation
    "mbr_join": mbr_join,
    # emits BENCH_service.json: warm micro-batched serving vs cold joins
    "service_throughput": service_throughput,
    # emits BENCH_pipeline.json: fused single-dispatch chain vs staged
    "pipeline_e2e": pipeline_e2e,
    # emits BENCH_scaleout.json: cost-balanced tiling vs the static grid
    "scaleout": scaleout,
}


def main() -> int:
    smoke = smoke_requested()
    failed = []
    want = [a for a in sys.argv[1:] if a != "--smoke"]
    print("name,us_per_call,derived")
    for name, mod in SUITES.items():
        if want and not any(w in name for w in want):
            continue
        t0 = time.time()
        try:
            if smoke:
                if hasattr(mod, "smoke"):
                    mod.smoke()
                else:
                    print(f"# suite {name} has no smoke mode, skipped")
                    continue
            else:
                for line in mod.run():
                    print(line)
        except Exception as e:  # run the other suites; fail at the end
            print(f"{name}_FAILED,0,{e!r}")
            failed.append(name)
        print(f"# suite {name} took {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# failed suites: {', '.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
