"""Storage-tier benchmark: f32 vs int8, resident vs mmap-streamed.

The paper's section 5 names quantization as the FQ-SD throughput lever and
section 3.3 streams partitions when the dataset outgrows device memory;
ISSUE 5 combines them: the out-of-core scan streams the int8 tier at
1 B/element and rescores only candidate rows of the f32 tier. This section
measures the full 2x2 (tier x residency) against the exact f32 baseline on
one batch shape, reporting the serving-relevant numbers — qps, p50/p99 per
call, dataset bytes moved per scan (the honest per-request account from
``SearchResult.stats``, i.e. codes + per-row channels + candidate reads on
the streamed int8 path), and the certified-exact fraction — into
BENCH_store.json. The acceptance ratio (streamed int8 bytes / streamed f32
bytes, expected <= ~0.3 at these sizes) rides the int8 row's
``bytes_ratio_vs_f32`` field.

ISSUE 6 adds the speculative overlapped gather to the streamed int8 path:
the int8 row now carries the phase split (scan_ms / gather_ms / rescore_ms),
the speculation counters, and ``p50_ratio_vs_resident_int8`` (the pipeline
acceptance metric — streamed p50 within ~1.1x of resident int8 at bench
scale); a ``_nospec`` companion row (spec_trigger=1.0) isolates what the
overlap buys. Results are bit-identical on both rows by construction.

ISSUE 8 adds the degraded-mode row: the streamed int8 scan with one shard
persistently unreadable (``store/int8_mmap_streamed_degraded``) — the shard
quarantines to its f32 rows, the result stays certified exact, and the row
prices the self-healing overhead (``p50_ratio_vs_healthy``,
``degraded_shards``) in the same trajectory DB.

ISSUE 7 adds the mesh subsection: the same tier pair on a device group —
resident row-sharded int8 (fdsq-sharded-int8) and the out-of-core ring
stream (fqsd-sharded-int8-streamed) — reporting qps, p50, per-device scan
bytes, ``bytes_ratio_vs_f32``, and modeled joules/query (device TDP x
group size from ``repro.roofline.hw``; a proxy, labeled as such, and
left out on a device kind the peaks table does not list). The mesh rows
run in-process when the process has two or more devices: a multi-chip
host, or CPU devices forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. With one device
the bench says they were not run.
"""
from __future__ import annotations

import sys
import tempfile

import numpy as np

from benchmarks.common import RESULTS, emit, energy_j, time_samples
from repro.api import SearchRequest
from repro.core import ExactKNN
from repro.faults import FaultInjector, FaultPlan
from repro.store import DatasetStore

K = 10
REPEATS = 7


def _pcts(times: list[float], m: int) -> tuple[float, float, float]:
    arr = np.asarray(times)
    return (float(np.percentile(arr, 50) * 1e6),
            float(np.percentile(arr, 99) * 1e6),
            float(m / np.median(arr)))


def _bench(eng: ExactKNN, q: np.ndarray, tier: str, repeats: int, **req_kw):
    req = SearchRequest(queries=q, tier=tier, **req_kw)
    call = lambda: eng.search(req).topk
    t = time_samples(call, repeats=repeats)
    res = eng.search(req)  # one counted call for stats/certificate
    p50, p99, qps = _pcts(t, q.shape[0])
    cert = float(np.mean(np.asarray(res.certified)))
    return p50, p99, qps, int(res.stats["bytes_scanned"]), cert, res


def _phase_fields(res) -> dict:
    out = {}
    for key in ("scan_ms", "gather_ms", "rescore_ms"):
        if key in res.stats:
            out[key] = round(float(res.stats[key]), 3)
    spec = res.stats.get("speculation")
    if spec:
        out.update(spec_trigger=spec["trigger"],
                   rows_speculated=spec["rows_speculated"],
                   rows_topped_up=spec["rows_topped_up"],
                   rows_wasted=spec["rows_wasted"])
    return out


def run(quick: bool = False) -> None:
    n, d, m = (32768, 128, 16) if quick else (131072, 128, 64)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)

    # --- resident: exact f32 baseline vs the certified int8 tier ---------
    eng = ExactKNN(k=K).fit(x)
    p50, p99, qps, nbytes, cert, _ = _bench(eng, q, "f32", REPEATS)
    emit("store/f32_resident", p50, f"qps={qps:.0f}",
         tier="f32", residency="resident", qps=qps, p50_us=p50, p99_us=p99,
         bytes_scanned=nbytes, certified_exact=cert, n=n, d=d, m=m, k=K)

    eng.enable_int8()
    p50, p99, qps, nbytes, cert, _ = _bench(eng, q, "int8", REPEATS)
    resident_int8_p50 = p50
    emit("store/int8_resident", p50, f"qps={qps:.0f};certified={cert:.3f}",
         tier="int8", residency="resident", qps=qps, p50_us=p50, p99_us=p99,
         bytes_scanned=nbytes, certified_exact=cert, n=n, d=d, m=m, k=K)

    # --- out-of-core: the same tier pair through the mmap shard stream ---
    with tempfile.TemporaryDirectory() as tmp:
        store = DatasetStore.from_array(x, rows_per_shard=n // 8,
                                        directory=tmp)
        oeng = ExactKNN(k=K, device_budget_bytes=1).fit_store(store)
        repeats = max(2, REPEATS // 2)
        p50, p99, qps, f32_bytes, cert, _ = _bench(oeng, q, "f32", repeats)
        emit("store/f32_mmap_streamed", p50,
             f"qps={qps:.0f};shards={store.n_shards}",
             tier="f32", residency="mmap-streamed", qps=qps, p50_us=p50,
             p99_us=p99, bytes_scanned=f32_bytes, certified_exact=cert,
             n_shards=store.n_shards, n=n, d=d, m=m, k=K)

        oeng.enable_int8()
        # speculation off (trigger=1.0): every candidate row gathered only
        # after the final merge — the pre-ISSUE-6 serial schedule
        p50, p99, qps, i8_bytes, cert, res = _bench(
            oeng, q, "int8", repeats, spec_trigger=1.0)
        nospec_p50 = p50
        emit("store/int8_mmap_streamed_nospec", p50,
             f"qps={qps:.0f};certified={cert:.3f}",
             tier="int8", residency="mmap-streamed", qps=qps, p50_us=p50,
             p99_us=p99, bytes_scanned=i8_bytes, certified_exact=cert,
             n_shards=store.n_shards, n=n, d=d, m=m, k=K,
             **_phase_fields(res))

        # speculation on (tuned trigger if the device cache has one, else
        # the 0.5 default): gather overlaps the tail of the shard scan
        p50, p99, qps, i8_bytes, cert, res = _bench(oeng, q, "int8", repeats)
        ratio = i8_bytes / f32_bytes
        p50_ratio = p50 / resident_int8_p50
        emit("store/int8_mmap_streamed", p50,
             f"qps={qps:.0f};certified={cert:.3f};bytes={ratio:.2f}x_f32;"
             f"p50={p50_ratio:.2f}x_resident",
             tier="int8", residency="mmap-streamed", qps=qps, p50_us=p50,
             p99_us=p99, bytes_scanned=i8_bytes, certified_exact=cert,
             bytes_ratio_vs_f32=ratio,
             p50_ratio_vs_resident_int8=p50_ratio,
             p50_ratio_vs_nospec=p50 / nospec_p50,
             n_shards=store.n_shards, n=n, d=d, m=m, k=K,
             **_phase_fields(res))

        # degraded mode (ISSUE 8): one int8 shard persistently unreadable —
        # the scan quarantines it and reads its f32 rows instead, so the
        # result stays certified exact; this row prices that self-healing
        # (more bytes moved, lower qps) so the resilience cost is tracked
        # by the same trajectory gate as the healthy rows
        store.fault_injector = FaultInjector(
            FaultPlan(fail_shards=(1,), fail_tier="int8"))
        try:
            dp50, dp99, dqps, d_bytes, dcert, dres = _bench(
                oeng, q, "int8", repeats, max_retries=0)
        finally:
            store.fault_injector = None
        degraded = dres.stats["health"]["degraded"]
        emit("store/int8_mmap_streamed_degraded", dp50,
             f"qps={dqps:.0f};certified={dcert:.3f};"
             f"quarantined={len(degraded)};p50={dp50 / p50:.2f}x_healthy",
             tier="int8", residency="mmap-streamed", qps=dqps, p50_us=dp50,
             p99_us=dp99, bytes_scanned=d_bytes, certified_exact=dcert,
             degraded_shards=len(degraded),
             p50_ratio_vs_healthy=dp50 / p50,
             n_shards=store.n_shards, n=n, d=d, m=m, k=K,
             **_phase_fields(dres))

    # --- compaction churn (ISSUE 10): journaled mutations inflate the
    # int8 scan (delta rows have no int8 representation, so they stream
    # as f32), then a background-style fold + atomic generation swap
    # re-quantizes them; this row tracks the bytes_ratio_vs_f32 on both
    # sides of the swap so compaction's bandwidth payoff — and its cost
    # (fold wall time per live row) — ride the trajectory gate
    with tempfile.TemporaryDirectory() as tmp:
        DatasetStore.from_array(x, rows_per_shard=n // 8, directory=tmp,
                                tiers=("f32", "int8")).close()
        store = DatasetStore.open(tmp)
        ceng = ExactKNN(k=K, device_budget_bytes=1).fit_store(store)
        ceng.enable_int8()
        repeats = max(2, REPEATS // 2)
        churn_rng = np.random.default_rng(1)
        ceng.upsert(churn_rng.standard_normal(
            (n // 16, d)).astype(np.float32))
        ceng.delete(list(churn_rng.choice(n, size=n // 32, replace=False)))
        _, _, _, f32_b, _, _ = _bench(ceng, q, "f32", repeats)
        _, _, _, i8_b, _, _ = _bench(ceng, q, "int8", repeats)
        ratio_before = i8_b / f32_b
        cstats = store.compact()  # fold + re-quantize + pointer swap
        p50, p99, qps, f32_a, cert, _ = _bench(ceng, q, "f32", repeats)
        p50, p99, qps, i8_a, cert, res = _bench(ceng, q, "int8", repeats)
        ratio_after = i8_a / f32_a
        emit("store/compaction_churn", p50,
             f"qps={qps:.0f};certified={cert:.3f};"
             f"bytes={ratio_before:.2f}->{ratio_after:.2f}x_f32;"
             f"fold={cstats['duration_s'] * 1e3:.0f}ms",
             tier="int8", residency="mmap-streamed", qps=qps, p50_us=p50,
             p99_us=p99, bytes_scanned=i8_a, certified_exact=cert,
             bytes_ratio_vs_f32=ratio_after,
             bytes_ratio_vs_f32_before_compaction=ratio_before,
             compaction_s=cstats["duration_s"],
             rows_reclaimed=cstats["rows_reclaimed"],
             delta_folded=cstats["delta_folded"],
             generation=cstats["generation"],
             n_shards=store.n_shards, n=store.n_live, d=d, m=m, k=K,
             **_phase_fields(res))

    # --- mesh: the same tier pair across a device group ------------------
    _mesh_section(quick)


def _mesh_section(quick: bool) -> None:
    """Run the mesh rows in this process when it has a device group. One
    process per chip: no child is started, since a parent that has touched
    JAX holds the chip a child would need."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"# store_bench: mesh rows not run: this process has {n_dev} "
              "device (they need >= 2)", file=sys.stderr)
        return
    _run_mesh(quick)


def _run_mesh(quick: bool) -> None:
    """The mesh rows proper; requires >1 jax device in this process."""
    import jax

    from repro.roofline.hw import peaks_for

    n, d, m = (32768, 128, 16) if quick else (131072, 128, 64)
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    # modeled energy: wall time x (device TDP x group size); a proxy, not a
    # measurement — see benchmarks/common.py. TPU rows only, from the
    # table keyed by device kind: an unlisted TPU is an error, and no
    # chip's TDP stands in for another (or for the CPU's).
    spec = peaks_for(dev.device_kind) if dev.platform == "tpu" else None

    def energy_fields(qps: float) -> dict:
        if spec is None:
            return {}
        return {"joules_per_query": energy_j(1.0, spec.tdp_watts * n_dev) / qps,
                "energy_model": f"{spec.name}_tdp_x{n_dev}"}
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)
    repeats = max(2, REPEATS // 2)

    with jax.set_mesh(mesh):
        # resident row-sharded certified int8 (fdsq-sharded-int8)
        eng = ExactKNN(k=K, mesh=mesh, mesh_axes=("data",))
        store = DatasetStore.from_array(x, row_mult=eng._row_mult(n),
                                        tiers=("f32", "int8"))
        eng.fit_store(store)
        p50, p99, qps, nbytes, cert, res = _bench(eng, q, "int8", repeats)
        per_dev = res.stats["bytes_per_device"]
        emit("store/mesh_int8_resident", p50,
             f"qps={qps:.0f};certified={cert:.3f};devs={n_dev}",
             tier="int8", residency="mesh-resident", qps=qps, p50_us=p50,
             p99_us=p99, bytes_scanned=nbytes, bytes_per_device=per_dev,
             certified_exact=cert, n_devices=n_dev, n=n, d=d, m=m, k=K,
             **energy_fields(qps))

        # out-of-core ring stream (fqsd-sharded-int8-streamed): one store,
        # shard i scans on device i mod P, nothing resident
        with tempfile.TemporaryDirectory() as tmp:
            store = DatasetStore.from_array(x, rows_per_shard=n // 8,
                                            directory=tmp)
            oeng = ExactKNN(k=K, mesh=mesh, mesh_axes=("data",),
                            device_budget_bytes=1).fit_store(store)
            oeng.enable_int8()
            p50, p99, qps, i8_bytes, cert, res = _bench(oeng, q, "int8",
                                                        repeats)
            per_dev = res.stats["bytes_per_device"]
            ratio = sum(per_dev) / store.nbytes("f32")
            emit("store/mesh_int8_ring_streamed", p50,
                 f"qps={qps:.0f};certified={cert:.3f};devs={n_dev};"
                 f"bytes={ratio:.2f}x_f32",
                 tier="int8", residency="mesh-ring-streamed", qps=qps,
                 p50_us=p50, p99_us=p99, bytes_scanned=i8_bytes,
                 bytes_per_device=per_dev, bytes_ratio_vs_f32=ratio,
                 certified_exact=cert, n_devices=n_dev,
                 n_shards=store.n_shards, n=n, d=d, m=m, k=K,
                 **_phase_fields(res), **energy_fields(qps))


if __name__ == "__main__":
    run(quick="--quick" in sys.argv[1:])
