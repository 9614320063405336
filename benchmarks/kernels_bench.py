"""Kernel/executor benchmark: every executor x storage tier, the fused
kernels' threshold-pruning skip rate on a warm-queue workload, and
autotuned vs default block shapes.

This section is the PR-over-PR perf trajectory for the execution layer:
``benchmarks/run.py --quick`` additionally copies its JSON to
``BENCH_kernels.json`` at the repo root, and CI uploads it as an artifact.
Rows carry qps / p50 / p99 / tier / executor (+ skip rate and tile shapes
for the fused kernels), so regressions are attributable to one executor.
"""
from __future__ import annotations

import tempfile

import numpy as np

from benchmarks.common import emit, time_samples
from repro.core import ExactKNN
from repro.store import DatasetStore
from repro.tuning import (
    AutotuneCache,
    autotune_knn,
    set_default_cache,
)

K = 10
M = 16  # query batch shared by every executor row
REPEATS = 3


def _pcts(times: list[float]) -> tuple[float, float, float]:
    arr = np.asarray(times)
    return (float(np.percentile(arr, 50) * 1e6),
            float(np.percentile(arr, 99) * 1e6),
            float(M / np.median(arr)))


def _emit_executor(eng: ExactKNN, name: str, call, repeats: int = REPEATS,
                   **extra) -> None:
    t = time_samples(call, repeats=repeats)
    p50, p99, qps = _pcts(t)
    plan = eng.plans[-1]
    assert plan.executor == name, (plan.executor, name)
    row = dict(executor=name, tier=plan.tier, qps=qps, p50_us=p50,
               p99_us=p99, m=M, k=K, **extra)
    ks = eng.last_kernel_stats
    if ks is not None:
        row["prune_skip_rate"] = float(ks["prune_skip_rate"])
        row["blocks"] = list(ks["blocks"])
    emit(f"kernels/{name}", p50, f"qps={qps:.0f};tier={plan.tier}", **row)


def run(quick: bool = False) -> None:
    n, d = (4096, 64) if quick else (32768, 128)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((M, d)).astype(np.float32)

    real_cache = AutotuneCache.for_device()
    set_default_cache(real_cache)
    try:
        _run_rows(quick, n, d, x, q, real_cache)
    finally:
        set_default_cache(None)


def _run_rows(quick, n, d, x, q, real_cache) -> None:
    # ---- resident XLA executors (f32 + int8 tiers) ----------------------
    eng = ExactKNN(k=K, n_partitions=4).fit(x)
    _emit_executor(eng, "fdsq-xla", lambda: eng.query(q))
    _emit_executor(eng, "fqsd-xla", lambda: eng.query_batch(q))
    eng.enable_int8()
    _emit_executor(eng, "fqsd-int8", lambda: eng.query_batch_int8(q))

    # ---- fused Pallas executors (f32 + int8 tiers) ----------------------
    pal = ExactKNN(k=K, backend="pallas").fit(x)
    _emit_executor(pal, "fdsq-pallas", lambda: pal.query_batch(q))
    pal.enable_int8()
    _emit_executor(pal, "fqsd-int8-pallas", lambda: pal.query_batch_int8(q))

    # ---- host-streamed executors ---------------------------------------
    stream_rows = max(256, n // 8)
    _emit_executor(
        eng, "fqsd-streamed",
        lambda: eng.search_streamed(q, x, rows_per_partition=stream_rows),
        repeats=max(2, REPEATS - 1),
    )
    with tempfile.TemporaryDirectory() as tmp:
        store = DatasetStore.from_array(x, rows_per_shard=stream_rows,
                                        directory=tmp)
        oeng = ExactKNN(k=K, device_budget_bytes=1).fit_store(store)
        _emit_executor(oeng, "fqsd-mmap-streamed",
                       lambda: oeng.query_batch(q),
                       repeats=max(2, REPEATS - 1), n_shards=store.n_shards)

    # ---- mesh executors (1x1 mesh off-cluster; exactness elsewhere) ----
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    meng = ExactKNN(k=K, mesh=mesh).fit(x)
    _emit_executor(meng, "fdsq-sharded", lambda: meng.query(q))
    _emit_executor(meng, "fqsd-sharded", lambda: meng.query_batch(q))

    # ---- threshold pruning on a warm-queue workload --------------------
    # nearest rows first: queues warm in the first tiles, later tiles are
    # provably worse, so the insertion filter should measurably fire.
    order = np.argsort(((q.mean(0)[None, :] - x) ** 2).sum(1))
    warm = ExactKNN(k=K, backend="pallas").fit(x[order])
    t = time_samples(warm.query_batch, q, repeats=REPEATS)
    p50, p99, qps = _pcts(t)
    sr = float(warm.last_kernel_stats["prune_skip_rate"])
    emit("kernels/prune_warm_queue", p50, f"skip_rate={sr:.3f}",
         executor="fdsq-pallas", tier="f32", qps=qps, p50_us=p50, p99_us=p99,
         prune_skip_rate=sr, workload="rows sorted nearest-first", m=M, k=K)

    # ---- autotuned vs default blocks -----------------------------------
    # the "default" row must plan against an EMPTY cache (a previously
    # persisted device cache would silently make this tuned-vs-tuned and
    # hide autotune regressions); the sweep writes to the real per-device
    # cache so CI machines accumulate warm starts, and the tuned row plans
    # against it.
    set_default_cache(AutotuneCache(path=None))
    fresh = ExactKNN(k=K, backend="pallas").fit(x)
    p_cold = fresh.plan_for("fqsd", M)
    assert (p_cold.block_m, p_cold.block_n, p_cold.block_d) == (0, 0, 0)
    t = time_samples(fresh.query_batch, q, repeats=REPEATS)
    p50_d, p99_d, qps_d = _pcts(t)
    blocks_d = fresh.last_kernel_stats["blocks"]
    emit("kernels/blocks_default", p50_d, f"blocks={blocks_d}",
         executor="fdsq-pallas", tier="f32", qps=qps_d, p50_us=p50_d,
         p99_us=p99_d, blocks=list(blocks_d), tuned=False)

    best, timings = autotune_knn(
        p_cold.m, p_cold.padded_rows, p_cold.padded_dim, k=K,
        cache=real_cache, repeats=1 if quick else 2,
        max_candidates=4 if quick else None,
    )
    set_default_cache(real_cache)
    tuned_eng = ExactKNN(k=K, backend="pallas").fit(x)
    p_tuned = tuned_eng.plan_for("fqsd", M)
    t = time_samples(tuned_eng.query_batch, q, repeats=REPEATS)
    p50_t, p99_t, qps_t = _pcts(t)
    emit("kernels/blocks_autotuned", p50_t,
         f"blocks={tuple(best)};candidates={len(timings)}",
         executor="fdsq-pallas", tier="f32", qps=qps_t, p50_us=p50_t,
         p99_us=p99_t, blocks=list(best), tuned=True,
         n_candidates=len(timings),
         planner_blocks=[p_tuned.block_m, p_tuned.block_n,
                         p_tuned.block_d])
