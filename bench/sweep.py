"""Sweep the offered rate of an open-loop cell to find the highest rate the
server sustains, once, on the chip; the cell's traffic file then fixes its
rate below that.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds 10 \
        --rates 50,100,200,400 [--out chiprun_out/sweep.jsonl]

One process sets the cell up once and serves one window per rate, each on
a fresh server over the same collection. Per rate it prints the rate
answered, the latency percentiles from the due time, how the median
latency of the window's last fifth compares with its first fifth (a queue
that grows all through the window reads well above 1), the rows per
dispatch and the dispatches per plan mode.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import shutil
import sys
import time

from bench.cell import ROOT, load_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from bench import data, harness
    from bench.spans import Spans

    cell = load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("bench.sweep: the cell's traffic is not an open loop")
    harness.devices(cell.chips, require_tpu=True)
    harness.enable_compile_cache(harness.CACHE_DIR / "jax")
    from repro.tuning import AutotuneCache, set_default_cache

    set_default_cache(AutotuneCache(path=None))
    cfg = cell.config
    x = data.corpus(args.seed, cfg["rows"], cfg["dim"], cfg["data"])
    pool = data.query_pool(x, args.seed, int(cell.traffic["pool"]),
                           float(cell.traffic["query_noise"]))
    router = harness.build_router(cell, x)
    run_dir = harness.CACHE_DIR / "sweep"
    sink = open(args.out, "a") if args.out else None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            at = dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_qps=rate))
            shutil.rmtree(run_dir, ignore_errors=True)
            run_dir.mkdir(parents=True)
            np.savez(run_dir / "plan.npz",
                     **harness.make_plan(at, args.seed, args.seconds, pool))
            server = harness.make_server(at, router)
            harness.warm(server, at, pool)
            win = asyncio.run(harness.serve_window(
                server, at, args.seconds, run_dir, None, Spans()))
            res = harness.client_results(run_dir / "records.npz",
                                         args.seconds, win["t_start"],
                                         win["t_end"], "open")
            lat = res["latency_s"] * 1e3
            fifth = max(1, len(lat) // 5)
            st = server.schedulers[cfg["name"]].stats()
            d0, d1 = (win["marks"][k][1] for k in ("window_start",
                                                  "window_end"))
            row = {
                "cell": cell.name, "rate_qps": rate,
                "answered_qps": res["answered_in_window"] / args.seconds,
                "attempted": res["attempted"], "failed": res["failed"],
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "growth": float(np.median(lat[-fifth:]) / np.median(lat[:fifth])),
                "rows_per_dispatch": (res["answered_in_window"] / (d1 - d0)
                                      if d1 > d0 else None),
                "per_plan": {m: r["count"] for m, r in st["per_plan"].items()},
                "executors": {m: r["executors"] for m, r in st["per_plan"].items()},
                "lateness_p99_ms": res["lateness_p99_ms"],
                "t": time.monotonic(),
            }
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
        shutil.rmtree(run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
