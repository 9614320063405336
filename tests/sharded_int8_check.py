"""Mesh-scale certified int8 checks (ISSUE 7 tentpole).

Run standalone in a subprocess (4 fake CPU devices) by test_sharded_int8.py:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tests/sharded_int8_check.py
Prints "OK <name>" per check; exits non-zero on failure.

Every mesh int8 executor (resident fdsq-sharded-int8, ring-streamed
fqsd-sharded-int8, out-of-core fqsd-sharded-int8-streamed) must answer
bit-identically — values, indices, tie order — to the streamed f32
direct-form oracle on every adversarial quantization case, report honest
per-device scan bytes, survive upsert/delete/filter_mask without a single
recompile, and serve stores larger than the combined device budget.
"""
import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from adversarial_cases import QUANT_CASES  # noqa: E402
from repro.api import Router, SearchRequest  # noqa: E402
from repro.core import ExactKNN, cache_info, clear_executable_cache  # noqa: E402
from repro.core.fqsd import streamed_direct_scan  # noqa: E402
from repro.store import DatasetStore  # noqa: E402

N_DEV = 4


def check(name, cond):
    if not cond:
        raise SystemExit(f"FAIL {name}")
    print(f"OK {name}", flush=True)


def oracle(eng, q, k):
    """Streamed f32 direct-form oracle over the engine's own store view
    (same padded geometry, same validity channels) — the bit-identity
    reference every int8 executor is held to."""
    return streamed_direct_scan(eng._pad_queries(q),
                                eng.store.shard_source("f32"), k)


def assert_bitwise(name, res, orc):
    np.testing.assert_array_equal(np.asarray(res.topk.scores),
                                  np.asarray(orc.scores))
    np.testing.assert_array_equal(np.asarray(res.topk.indices),
                                  np.asarray(orc.indices))
    check(name, True)


def fit_mesh_resident(x, k, mesh, **kw):
    """Mesh-resident engine over a two-tier store (row-sharded int8 view)."""
    eng = ExactKNN(k=k, mesh=mesh, mesh_axes=("data",), **kw)
    store = DatasetStore.from_array(x, row_mult=eng._row_mult(x.shape[0]),
                                    tiers=("f32", "int8"))
    return eng.fit_store(store)


def fit_mesh_ring(x, k, mesh, rows_per_shard, directory=None, **kw):
    """Non-resident engine whose int8 shards ring-stream over the mesh."""
    store = DatasetStore.from_array(x, rows_per_shard=rows_per_shard,
                                    directory=directory)
    eng = ExactKNN(k=k, mesh=mesh, mesh_axes=("data",),
                   device_budget_bytes=1, **kw).fit_store(store)
    return eng.enable_int8()


def check_resident_quant_cases(mesh):
    for name in sorted(QUANT_CASES):
        q, x, k = QUANT_CASES[name]()
        eng = fit_mesh_resident(x, k, mesh)
        res = eng.search(SearchRequest(queries=q, tier="int8"))
        assert res.plan.executor == "fdsq-sharded-int8", res.plan.executor
        assert res.plan.mode == "fdsq-sharded-int8" and res.tier == "int8"
        per_dev = res.stats["bytes_per_device"]
        assert len(per_dev) == N_DEV and all(b > 0 for b in per_dev)
        cert = np.asarray(res.certified)
        assert cert.shape == (q.shape[0],) and cert.dtype == bool
        assert_bitwise(f"resident int8 == f32 oracle [{name}]",
                       res, oracle(eng, q, k))


def check_ring_quant_cases(mesh):
    for name in sorted(QUANT_CASES):
        q, x, k = QUANT_CASES[name]()
        # 384-row shards: 1024-row cases split into 3 shards — a shard
        # count the 4-device ring does NOT divide evenly
        eng = fit_mesh_ring(x, k, mesh, rows_per_shard=384)
        res = eng.search(SearchRequest(queries=q, tier="int8"))
        assert res.plan.executor == "fqsd-sharded-int8", res.plan.executor
        assert res.plan.mode == "fqsd-sharded-int8" and res.tier == "int8"
        assert len(res.stats["bytes_per_device"]) == N_DEV
        assert_bitwise(f"ring int8 == f32 oracle [{name}]",
                       res, oracle(eng, q, k))
    check("ring shard count not divisible by device count "
          f"(3 shards / {N_DEV} devices)", True)


def check_out_of_core(mesh, tmpdir):
    """A store larger than per-device budget x device count serves exactly
    via out-of-core mesh streaming — zero recompiles on repeat searches."""
    rng = np.random.default_rng(17)
    n, d, k = 4096, 128, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    store = DatasetStore.from_array(x, rows_per_shard=512, directory=tmpdir)
    budget = store.nbytes("f32") // (2 * N_DEV)  # per-device share, halved
    assert store.nbytes("f32") > budget * N_DEV
    eng = ExactKNN(k=k, mesh=mesh, mesh_axes=("data",),
                   device_budget_bytes=budget).fit_store(store)
    eng.enable_int8()
    res = eng.search(SearchRequest(queries=q, tier="int8"))
    assert res.plan.executor == "fqsd-sharded-int8-streamed", res.plan.executor
    assert_bitwise("out-of-core mesh stream == f32 oracle",
                   res, oracle(eng, q, k))
    check("store exceeds per-device budget x devices "
          f"({store.nbytes('f32')} B > {budget * N_DEV} B)", True)

    warm = cache_info()["misses"]
    res2 = eng.search(SearchRequest(queries=q, tier="int8"))
    assert cache_info()["misses"] == warm
    np.testing.assert_array_equal(np.asarray(res.topk.indices),
                                  np.asarray(res2.topk.indices))
    check("repeat out-of-core mesh search: zero recompiles", True)

    # the quantized mesh scan moves <= ~0.35x the f32 bytes per device
    # (codes + 12 B/row side channels vs 4 B/element; the candidate gather
    # is charged to the total, not the scan split)
    f32 = eng.search(SearchRequest(queries=q))
    f32_per_dev = f32.stats["bytes_scanned"] / N_DEV
    per_dev = res.stats["bytes_per_device"]
    ratio = max(per_dev) / f32_per_dev
    check(f"per-device int8 scan bytes ratio {ratio:.3f} <= 0.35",
          ratio <= 0.35)
    assert sum(per_dev) < res.stats["bytes_scanned"]  # gather adds traffic


def check_mesh_mutation_and_mask(mesh):
    """Delta shards + tombstones + filter_mask fold through the mesh
    executors with zero recompiles (ISSUE 7 satellite 1)."""
    rng = np.random.default_rng(23)
    n, d, k = 1024, 32, 5
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((4, d)).astype(np.float32)
    eng = fit_mesh_resident(x, k, mesh)
    # warm both tiers AND the delta-fold path (the delta-merge step, like
    # every step, compiles once ever — the invariant under churn is that
    # NOTHING new compiles after that)
    warm_ids = eng.upsert(np.zeros((1, d), np.float32))
    eng.search(SearchRequest(queries=q, tier="int8"))
    eng.search(SearchRequest(queries=q))
    eng.delete(warm_ids)
    warm = cache_info()["misses"]

    ids = eng.upsert((q[:2] + 1e-4).astype(np.float32))
    eng.delete([int(ids[0]), 3])
    mask = np.ones(eng.n_ids, dtype=bool)
    mask[[7, 11, int(ids[1])]] = False
    r8 = eng.search(SearchRequest(queries=q, tier="int8", filter_mask=mask))
    rf = eng.search(SearchRequest(queries=q, filter_mask=mask))
    check("mesh upsert/delete/mask: zero recompiles "
          f"(misses {cache_info()['misses']} == {warm})",
          cache_info()["misses"] == warm)

    # float64 brute force over the live, mask-eligible row set (ids are
    # never reused: the tombstoned warm-up row still occupies its slot)
    live = np.concatenate([x, np.zeros((1, d), np.float32),
                           (q[:2] + 1e-4).astype(np.float32)])
    keep = mask.copy()
    keep[[int(warm_ids[0]), int(ids[0]), 3]] = False  # tombstones
    gids = np.arange(live.shape[0])[keep]
    dist = ((q.astype(np.float64)[:, None, :]
             - live[keep].astype(np.float64)[None, :, :]) ** 2).sum(-1)
    order = np.argsort(dist, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.asarray(r8.topk.indices), gids[order])
    np.testing.assert_allclose(np.asarray(r8.topk.scores),
                               np.take_along_axis(dist, order, 1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(rf.topk.indices), gids[order])
    check("mesh int8 + f32 exact under upsert/delete/mask", True)


def check_scheduler_mesh_stats(mesh):
    """AdaptiveScheduler aggregates per-device bytes + phase timings for
    mesh dispatches exactly like streamed ones (ISSUE 7 satellite 2)."""
    from repro.serving import AdaptiveScheduler

    rng = np.random.default_rng(31)
    x = rng.standard_normal((1024, 24)).astype(np.float32)
    eng = fit_mesh_ring(x, 4, mesh, rows_per_shard=384)
    s = AdaptiveScheduler(eng, policy="throughput", int8_min_depth=4)
    reqs = [SearchRequest(queries=x[i], rid=i, arrival_s=0.0)
            for i in range(12)]
    results = list(s.serve(iter(reqs)))
    for r in results:
        assert int(r.indices[0]) == r.rid  # rows find themselves
    st = s.stats()
    assert st["per_plan"]["fqsd-int8"]["executors"] == ["fqsd-sharded-int8"]
    assert st["per_plan"]["fqsd-int8"]["tier"] == ["int8"]
    assert len(st["bytes_per_device"]) == N_DEV
    assert sum(st["bytes_per_device"]) > 0
    assert st["phase_ms"]["scan_ms"] >= 0.0
    check("scheduler aggregates mesh per-device bytes + phases", True)


def check_router_placement():
    """Router places a collection's shards across a device group and the
    placed collections share the process-wide executable cache."""
    rng = np.random.default_rng(41)
    x = rng.standard_normal((1024, 48)).astype(np.float32)
    q = rng.standard_normal((2, 48)).astype(np.float32)
    router = Router()
    router.create("a", store=DatasetStore.from_array(x, row_mult=512), k=5,
                  devices=N_DEV)
    assert router.engine("a").mesh is not None
    res = router.search("a", SearchRequest(queries=q, mode_hint="fdsq"))
    assert res.plan.executor == "fdsq-sharded", res.plan.executor
    st = router.stats()
    assert len(st["collections"]["a"]["devices"]) == N_DEV
    check("router places collection over the device group", True)

    warm = cache_info()["misses"]
    router.create("b", store=DatasetStore.from_array(
        rng.standard_normal((1024, 48)).astype(np.float32), row_mult=512),
        k=5, devices=N_DEV)
    router.search("b", SearchRequest(queries=q, mode_hint="fdsq"))
    check("same-geometry collection on same devices: zero new compiles",
          cache_info()["misses"] == warm)

    router.create("c", store=DatasetStore.from_array(
        x, row_mult=512, tiers=("f32", "int8")), k=5, devices=N_DEV)
    r8 = router.search("c", SearchRequest(queries=q, tier="int8"))
    assert r8.plan.executor == "fdsq-sharded-int8"
    assert router.stats()["collections"]["c"]["bytes_scanned"]["int8"] > 0
    check("router-placed collection serves the mesh int8 tier", True)


def main():
    assert len(jax.devices()) == N_DEV, jax.devices()
    mesh = jax.make_mesh((N_DEV,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    clear_executable_cache()
    with jax.set_mesh(mesh):
        check_resident_quant_cases(mesh)
        check_ring_quant_cases(mesh)
        with tempfile.TemporaryDirectory() as tmpdir:
            check_out_of_core(mesh, tmpdir)
        check_mesh_mutation_and_mask(mesh)
        check_scheduler_mesh_stats(mesh)
    check_router_placement()
    print("ALL_OK", flush=True)


if __name__ == "__main__":
    main()
