"""Run one cell of the benchmark on the chip this process is started on.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the float64
reference beside its limit. The same numbers are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits with 3.
"""
from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench.cell import ROOT, CellError, load_cell  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench.run: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
    except CellError as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    from bench import harness

    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t0=T0)
    except harness.NoAccelerator as e:
        print(f"bench.run: {e}; no result", file=sys.stderr)
        return 3
    for line in harness.format_checks(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(harness.jsonable(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
