"""Find a cell's configuration, traffic and metrics by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    bench/configs/<config>.json     sizes, metric, engine and server settings
    bench/traffic/<traffic>.json    the load generator's parameters
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/peaks.json                published peaks, keyed by device kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CellError(ValueError):
    """The cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # BENCHMARK.json metric entries that apply to the cell
    per_layer: tuple


def _load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"{path} does not exist") from None


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: pathlib.Path | None = None) -> Cell:
    """The cell `name` of ``BENCHMARK.json``, with its files read."""
    spec = _load_json(benchmark or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)),
    )


def load_reader(metric: str):
    """The ``read(records)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"per-layer metric {metric!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """Published peaks of `device_kind`; a kind not in the table is an error."""
    table = _load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise CellError(f"no published peaks for device kind {device_kind!r} "
                        f"in bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
