"""Readings for the limits of ``correct``: the program's on many seeds and
the control's (``bench.control``) on the same corpora and queries.

    python3 -m bench.calibrate --workload <cell> --seeds 11,12,... \
        --seconds 5 [--control] [--out chiprun_out/calib.jsonl]

One process runs the whole cell for each seed in turn (set-up, a short
window at the cell's own load, the check against the float64 reference
with no limit applied) and prints one JSON line per seed: the program's
compared numbers and, with ``--control``, the control's on the answers the
run checked. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench.cell import ROOT, load_cell

NO_LIMIT = {"bad_answers": float("inf"), "score_err": float("inf"),
            "rank_gap": float("inf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from bench import control, harness

    cell = load_cell(args.workload)
    cfg, k = cell.config, int(cell.traffic["k"])
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.monotonic()
            out = harness.run_cell(cell, seed, args.seconds, False, t0=t0,
                                   check_limits=NO_LIMIT, keep_inputs=True)
            inputs = out.pop("inputs")
            row = {"cell": cell.name, "seed": seed,
                   "program": inputs["readings"].numbers(),
                   "checked": inputs["readings"].checked,
                   "metrics": {m: v["value"] for m, v in out["metrics"].items()},
                   "attempted": out["attempted"], "failed": out["failed"]}
            if args.control:
                x, pool = inputs["x"], inputs["pool"]
                entries = np.unique(inputs["check"][0])
                t = time.monotonic()
                ids, scores = control.control_topk(x, pool[entries], k,
                                                   cfg["metric"])
                row["control"] = inputs["reference"].compare(
                    entries, ids, scores).numbers()
                row["control_s"] = time.monotonic() - t
            del inputs
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
