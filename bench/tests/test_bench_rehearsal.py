"""A CPU rehearsal of whole runs at a tiny size, and the refusals of a run
that has no chip or no program.

The rehearsal (``bench.tests.rehearse``) runs in a child process with its
own time limit: the client child against an in-process ``KnnServer``,
answers checked through the float64 reference, once whole and once with
each fault the cells can have planted underneath.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.cell import ROOT

RUNS = ("closed:1:0:none", "open:1:0:none", "closed:1:1:none",
        "closed:1:0:alter", "closed:1:0:half")
REHEARSAL_TIMEOUT_S = 240


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), env.get("PYTHONPATH", "")])
    return env


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    out = tmp_path_factory.mktemp("rehearsal")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse", str(out), *RUNS],
        cwd=ROOT, env=_env(), capture_output=True, text=True,
        timeout=REHEARSAL_TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = dict(line.split("\t", 1) for line in proc.stdout.splitlines()
                 if "\t" in line)
    return {run: json.loads(line) for run, line in lines.items()}


@pytest.mark.parametrize("run", ["closed:1:0:none", "open:1:0:none"])
def test_a_sound_run_is_correct_and_reports_its_metrics(rehearsal, run):
    out = rehearsal[run]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["bad_answers"] == {"value": 0, "limit": 0}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    m = out["metrics"]
    assert m["setup_s"]["value"] > 0
    if run.startswith("closed"):
        assert m["throughput_qps"]["value"] > 0
    else:
        assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]


def test_a_traced_run_reports_host_counters_and_no_device_number_on_cpu(
        rehearsal):
    out = rehearsal["closed:1:1:none"]
    assert out["correct"] is True
    m = out["metrics"]
    assert m["rows_per_dispatch.fqsd"]["value"] >= 1
    assert m["codec_ms_per_query.fqsd"]["value"] > 0
    assert m["executable_misses.fqsd"]["value"] == 0
    # the CPU has no device trace: nothing read from it is reported
    for name in ("device_idle_pct.fqsd", "device_ms_per_query.fdsq",
                 "fqsd_dispatch_roofline", "fdsq_dispatch_roofline"):
        assert name not in m
    assert "busy_s" not in out["device"] and "breakdown" not in out


@pytest.mark.parametrize("run", ["closed:1:0:alter", "closed:1:0:half"])
def test_a_broken_served_path_comes_out_not_correct(rehearsal, run):
    out = rehearsal[run]
    assert out["correct"] is False
    assert out["checks"]["score_err"]["value"] > out["checks"]["score_err"]["limit"]


def test_run_without_a_tpu_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "gist1m-l2.closed512-k1024", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "gist1m-l2.closed512-k1024", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
