"""The control at a size a test run holds: each cell's reference computed
at three bf16 passes (``bench.control``), put in the program's place, must
come out not correct under the cell's own limits, while the exact answer
rounded to float32 must pass them. On the chip the control runs at the
cells' own sizes (``bench.calibrate --control``, readings in PERF.md)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import control, data, reference
from bench.cell import ROOT, load_cell

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
ROWS, QUERIES = 32768, 12


@pytest.fixture(scope="module", params=CELLS)
def case(request):
    from bench import harness

    cell = load_cell(request.param)
    cfg, tr = cell.config, cell.traffic
    x = data.corpus(7, ROWS, cfg["dim"], cfg["data"])
    pool = data.query_pool(x, 7, QUERIES, float(tr["query_noise"]))
    entries = np.arange(QUERIES)
    ref = reference.Reference(x, pool, cfg["metric"], int(tr["k"]), entries)
    return cell, x, pool, entries, ref, harness.limits(cell.name)


def test_the_control_is_not_correct(case):
    cell, x, pool, entries, ref, lim = case
    ids, scores = control.control_topk(x, pool, int(cell.traffic["k"]),
                                       cell.config["metric"])
    got = ref.compare(entries, ids, scores).numbers()
    assert any(got[name] > lim[name] for name in lim), (got, lim)


def test_the_exact_answer_in_float32_is_correct(case):
    cell, x, pool, entries, ref, lim = case
    scores = ref.scores.astype(np.float32)
    got = ref.compare(entries, ref.ids, scores).numbers()
    assert all(got[name] <= lim[name] for name in lim), (got, lim)
