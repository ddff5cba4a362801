# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: reproduces every paper table (sections 5.4-5.10 +
Appendices B/C) on the synthetic Table-3 twin datasets.

    PYTHONPATH=src python -m benchmarks.run            # CI-sized
    PYTHONPATH=src python -m benchmarks.run --full     # paper-sized (200 sets)
    PYTHONPATH=src python -m benchmarks.run --suite wide_ops --quick \
        --out BENCH_candidate.json                     # CI regression gate

``--suite`` selects table/suite names (comma list; alias of the older
``--only``).  Suites ``wide_ops`` and ``wide_ops_sharded`` additionally
emit JSON records; ``--quick`` shrinks them to a gate-sized subset whose
(bench, dist, k) keys are a strict subset of the full sweep's.  The
sharded suite only exercises real sharding when more than one device is
visible (CI forces 4 with ``XLA_FLAGS=--xla_force_host_platform_device_
count=4``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized: 200 sets/dataset, ClusterData x50")
    ap.add_argument("--only", "--suite", dest="suites", default="",
                    help="comma list: table3,...,table14,kernels,"
                         "wide_ops,wide_ops_sharded,pairwise,"
                         "arena_warm,cold_start,query_throughput,"
                         "similar_sharded,wide_ops_arena_sharded")
    ap.add_argument("--quick", action="store_true",
                    help="gate-sized wide_ops sweeps (subset of full keys)")
    ap.add_argument("--out", default="",
                    help="write wide-op JSON records here instead of "
                         "BENCH_wide_ops.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])
    from benchmarks import ablation, kernels_bench, tables
    n_sets = 200 if args.full else 40
    n_time = 200 if args.full else 24
    cluster_scale = 0.1 if args.full else 0.002

    rows: list = []
    print("name,us_per_call,derived")
    want = set(args.suites.split(",")) if args.suites else None

    def go(name, fn):
        if want is None or name in want:
            fn()

    go("table3", lambda: tables.table3_datasets(rows, n_sets))
    go("table4", lambda: tables.table4_memory(rows, n_sets))
    go("table5", lambda: tables.table5_sequential(rows, n_time))
    go("table6", lambda: tables.table6_membership(rows, n_time))
    go("table7", lambda: tables.table7_pairwise_ops(rows, n_time))
    go("table8", lambda: tables.table8_wide_union(rows, n_time))
    go("table9", lambda: tables.table9_fast_counts(rows, n_time))
    go("table10", lambda: ablation.table10_simd_ablation(rows))
    go("table12", lambda: tables.table12_clusterdata(
        rows, scale=cluster_scale))
    go("table14", lambda: ablation.table14_host_vs_device(rows))
    go("kernels", lambda: kernels_bench.kernel_sweeps(rows))

    records: list = []
    if want is None or "wide_ops" in want:
        records += kernels_bench.wide_ops(rows, quick=args.quick)
    if want is None or "wide_ops_sharded" in want:
        records += kernels_bench.wide_ops_sharded(rows, quick=args.quick)
    if want is None or "pairwise" in want:
        records += kernels_bench.pairwise_suite(rows, quick=args.quick)
    if want is None or "arena_warm" in want:
        records += kernels_bench.arena_warm(rows, quick=args.quick)
    if want is None or "cold_start" in want:
        records += kernels_bench.cold_start(rows, quick=args.quick)
    if want is None or "query_throughput" in want:
        records += kernels_bench.query_throughput(rows, quick=args.quick)
    if want is None or "similar_sharded" in want:
        records += kernels_bench.similar_sharded(rows, quick=args.quick)
    if want is None or "wide_ops_arena_sharded" in want:
        records += kernels_bench.wide_ops_arena_sharded(
            rows, quick=args.quick)
    if records:
        out = args.out or "BENCH_wide_ops.json"
        with open(out, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {out}", file=sys.stderr)

    print(f"# {len(rows)} rows", file=sys.stderr)


if __name__ == "__main__":
    main()
