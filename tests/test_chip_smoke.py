"""chip_smoke.py off the chip: its --tiny rehearsal runs every phase on the
CPU and never prints the result line; without --tiny it refuses to run
where JAX finds no TPU, and it refuses to run outside a checkout. Also
where the entry points place JAX's compile cache."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _run(args, cwd=ROOT, script=SCRIPT, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _has_result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_tiny_rehearsal_runs_every_phase_without_result_line():
    proc = _run(["--tiny"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    for phase in ("== phase a", "== phase b", "== phase c"):
        assert phase in out
    for executor in ("fdsq-xla", "fqsd-xla", "fqsd-int8", "fdsq-pallas",
                     "fqsd-int8-pallas", "fqsd-mmap-streamed",
                     "fqsd-int8-mmap-streamed"):
        assert f"executor={executor} " in out, executor
    assert "refused by the planner" in out
    assert "FAILED" not in out
    assert "all phases passed" in out
    assert not _has_result_line(out)


def test_tiny_mesh_rehearsal_runs_the_sharded_phase_only():
    proc = _run(["--tiny", "--chips", "4"], extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "== phase mesh" in out
    assert "== phase a" not in out and "== phase c" not in out
    for executor in ("fdsq-sharded", "fqsd-sharded", "fdsq-sharded-int8",
                     "fqsd-sharded-int8"):
        assert f"executor={executor} " in out, executor
        assert f"{executor} vs oracle" in out, executor
    assert "all phases passed" in out
    assert not _has_result_line(out)


def test_refuses_without_a_tpu_before_any_phase():
    proc = _run([])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "== phase" not in proc.stdout
    assert not _has_result_line(proc.stdout)


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run([], cwd=tmp_path, script=alone)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax_cache"])
def test_compile_cache_placement(env_dir):
    """Entry points keep JAX's compile cache in $JAX_COMPILATION_CACHE_DIR
    when it is set (leaving it to JAX), else at one fixed checkout path."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    placed, configured = proc.stdout.split()
    want = env_dir or str(ROOT / "artifacts" / "jax_cache")
    assert placed == configured == want
