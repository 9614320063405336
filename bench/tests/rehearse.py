"""A whole run of the harness on the CPU at a tiny size, for the tests.

    python3 -m bench.tests.rehearse <out_dir> <loop>:<seconds>:<trace>:<fault> ...

Each argument after the directory is one run: loop ``closed`` or ``open``,
the window's seconds, ``--trace`` 0 or 1, and a fault that breaks the
served path underneath the harness (``bench.tests.faults``; ``none``
leaves it whole). The runs share one process, so only the first compiles.
Each skips the harness's look for a chip and runs everything else: corpus,
server, warm-up, the client child process, the window and the check
against the float64 reference. Each prints one line to standard output:
the run's argument, a tab, and the run's JSON line.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

T0 = time.monotonic()

from bench.cell import ROOT, Cell  # noqa: E402

CONFIG = {
    "name": "tiny-l2", "rows": 3000, "dim": 40, "metric": "l2",
    "engine": {"k": 16, "backend": "xla", "n_partitions": 2},
    "server": {"policy": "adaptive", "fdsq_max_batch": 4, "fqsd_min_depth": 4,
               "max_batch": 8, "max_inflight": 64},
    "data": {"clusters": 8, "center_scale": 2.0, "noise": 0.5},
}
TRAFFIC = {
    "closed": {"loop": "closed", "connections": 8, "k": 16, "pool": 64,
               "query_noise": 0.3, "sample": 16, "sample_span": 32},
    "open": {"loop": "open", "arrivals": "poisson", "rate_qps": 150.0,
             "k": 16, "pool": 64, "query_noise": 0.3, "sample": 16,
             "connections": 4},
}
END_TO_END = (
    {"name": "throughput_qps", "unit": "queries/s"},
    {"name": "latency_p50_ms", "unit": "ms"},
    {"name": "latency_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"},
)
#: wide enough for float32 rounding at this size, far below a wrong row
LIMITS = {"bad_answers": 0, "score_err": 64.0, "rank_gap": 64.0}


def per_layer() -> tuple:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return tuple({"name": m["name"], "unit": m["unit"]}
                 for m in spec["per_layer"])


def main(argv) -> int:
    out_dir, runs = pathlib.Path(argv[0]), argv[1:]
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    from bench.tests import faults

    for i, run in enumerate(runs):
        loop, seconds, trace, fault = run.split(":")
        cell = Cell(name=f"tiny.{loop}", chips=1, config=CONFIG,
                    traffic=TRAFFIC[loop], end_to_end=END_TO_END,
                    per_layer=per_layer())
        undo = faults.install(fault)
        try:
            out = harness.run_cell(
                cell, 2**40 + 7, float(seconds), bool(int(trace)),
                t0=T0 if i == 0 else time.monotonic(), require_tpu=False,
                run_dir=out_dir / str(i), compile_cache=None,
                check_limits=LIMITS)
        finally:
            undo()
        print(f"{run}\t{json.dumps(harness.jsonable(out))}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
