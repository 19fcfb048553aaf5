"""Benchmark entrypoint. One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes ``BENCH_coadd.json``
(per-method us/image + before/after dispatch counts for the device-resident
coadd engine).

  python -m benchmarks.run             # everything
  python -m benchmarks.run --fast      # skip the slow Table-1 timing loops
  python -m benchmarks.run --quick     # CI smoke: coadd engine report only
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke lane: only the coadd engine report "
                         "(BENCH_coadd.json incl. batched rows, the "
                         "sparse-vs-dense selectivity sweep, and the "
                         "serving queries/sec-under-concurrency rows), "
                         "one repeat")
    ap.add_argument("--coadd-json", default="BENCH_coadd.json",
                    help="where to write the coadd engine dispatch/latency report")
    args = ap.parse_args()

    from benchmarks import kernel_bench, paper_tables
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    t0 = time.perf_counter()
    rows = ["name,us_per_call,derived"]
    if args.quick:
        rows += kernel_bench.bench_coadd_engine(
            out_path=args.coadd_json, repeats=1
        )
        print("\n".join(rows))
        print(f"# total_bench_wall_s={time.perf_counter()-t0:.1f}", file=sys.stderr)
        return
    rows += paper_tables.bench_table2()
    rows += paper_tables.bench_consistency()
    rows += paper_tables.bench_fig8_breakdown()
    if not args.fast:
        rows += paper_tables.bench_table1()
    # Always write the dispatch-count report (it's the PR-over-PR perf
    # trajectory), but keep --fast fast: one timed repeat instead of three.
    rows += kernel_bench.bench_coadd_engine(
        out_path=args.coadd_json, repeats=1 if args.fast else 3
    )
    rows += kernel_bench.bench_mapper_throughput()
    rows += kernel_bench.bench_warp_pallas_interpret()
    rows += kernel_bench.bench_flash_attention()
    rows += kernel_bench.bench_ssd()
    print("\n".join(rows))
    print(f"# total_bench_wall_s={time.perf_counter()-t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
