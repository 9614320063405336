"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json-dir DIR]

Emits ``name,us_per_call,derived`` CSV rows (stdout), matching:
    table2/*     paper Table 2  (latency / throughput / energy, 3 datasets)
    table3/*     paper Table 3  (cutoff k vs parallelism trade-off)
    chipknn/*    section 4.6    (GB/s vs dimension, CHIP-KNN comparison)
    roofline/*   EXPERIMENTS.md Roofline (from dry-run artifacts)
    store/*      DatasetStore tiers (f32 / int8 / mmap-streamed)
    kernels/*    executor x tier sweep, pruning skip-rate, autotuned blocks

Every section additionally lands as machine-readable
``<json-dir>/BENCH_<section>.json`` (qps, p50/p99, bytes scanned per tier,
certification rate) so the perf trajectory is trackable across PRs.
``artifacts/bench/BENCH_kernels.json`` is the CI artifact tracking the
execution-layer trajectory; a convenience mirror is also written to
``BENCH_kernels.json`` at the repo root. Both live in .gitignore — they
are regenerated on every run and must never be committed. Every run is
also appended to ``<json-dir>/trajectory.sqlite`` (see
``benchmarks.trajectory``), whose compare CLI is CI's regression gate
against the previous run's ``BENCH_store.json`` artifact.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table2,table3,chipknn,"
                         "roofline,store,kernels,serve")
    ap.add_argument("--json-dir", default="artifacts/bench",
                    help="directory for BENCH_<section>.json outputs")
    ap.add_argument("--kernels-json", default="BENCH_kernels.json",
                    help="untracked repo-root mirror of the kernels section "
                         "(CI uploads <json-dir>/BENCH_kernels.json)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        chipknn,
        common,
        kernels_bench,
        roofline_table,
        serve_bench,
        store_bench,
        table2,
        table3,
    )

    sections = {
        "table2": table2.run,
        "table3": table3.run,
        "chipknn": chipknn.run,
        "roofline": roofline_table.run,
        "store": store_bench.run,
        "kernels": kernels_bench.run,
        "serve": serve_bench.run,
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        try:
            sections[name](quick=args.quick)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0,ERROR", flush=True)
    for path in common.write_json(args.json_dir, quick=args.quick):
        print(f"# wrote {path}", file=sys.stderr)
    try:
        from benchmarks import trajectory
        db = trajectory.record(common.RESULTS, quick=args.quick,
                               db_path=os.path.join(args.json_dir,
                                                    "trajectory.sqlite"))
        print(f"# recorded trajectory in {db}", file=sys.stderr)
    except Exception:
        # trajectory recording is observability — never fail the bench run
        traceback.print_exc()
    kern_src = os.path.join(args.json_dir, "BENCH_kernels.json")
    if ("kernels" in common.RESULTS and os.path.exists(kern_src)
            and os.path.abspath(kern_src) != os.path.abspath(args.kernels_json)):
        shutil.copyfile(kern_src, args.kernels_json)
        print(f"# wrote {args.kernels_json}", file=sys.stderr)
    return failures


if __name__ == "__main__":
    sys.exit(main())
