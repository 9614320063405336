"""Per-device block-shape autotuner: cache robustness, sweep legality, and
the planner/executable-cache contract (tuned plans never recompile)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import ExactKNN, cache_info, clear_executable_cache
from repro.core.planner import DatasetStoreMeta, EngineConfig, plan
from repro.kernels.knn import ops as knn_ops
from repro.tuning import (
    AutotuneCache,
    BlockShapes,
    PipelineKnobs,
    autotune_knn,
    autotune_pipeline,
    candidate_blocks,
    lookup_blocks,
    lookup_pipeline,
    pipeline_key,
    set_default_cache,
    tuning_key,
)


@pytest.fixture(autouse=True)
def isolated_default_cache():
    """Never let tests read/write the real artifacts/autotune/ cache."""
    set_default_cache(AutotuneCache(path=None))
    yield
    set_default_cache(None)


KEY = tuning_key("fdsq-pallas", m=8, n=1024, d=128, dtype="float32",
                 metric="l2", k=10)


class TestCacheRoundTrip:
    def test_put_get_round_trip(self, tmp_path):
        path = str(tmp_path / "cpu.json")
        cache = AutotuneCache(path)
        assert cache.get(KEY) is None  # missing file == cold cache
        cache.put(KEY, BlockShapes(32, 512, 128), us_per_call=12.5)
        assert cache.get(KEY) == BlockShapes(32, 512, 128)
        # a fresh instance reads the persisted winner back
        reread = AutotuneCache(path)
        assert reread.get(KEY) == BlockShapes(32, 512, 128)
        payload = json.load(open(path))
        assert payload["schema_version"] == 1
        assert payload["entries"][KEY]["us_per_call"] == 12.5

    def test_missing_file_is_cold_not_fatal(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "never_written.json"))
        assert cache.get(KEY) is None
        assert len(cache) == 0

    def test_corrupted_json_is_cold_then_recovers(self, tmp_path):
        path = str(tmp_path / "cpu.json")
        with open(path, "w") as f:
            f.write("{ this is not json !!")
        cache = AutotuneCache(path)
        assert cache.get(KEY) is None  # corrupt == cold, never an exception
        # the next put() rewrites the file cleanly
        cache.put(KEY, BlockShapes(8, 256, 128))
        assert AutotuneCache(path).get(KEY) == BlockShapes(8, 256, 128)

    @pytest.mark.parametrize("payload", [
        '{"schema_version": 1, "entries": "nope"}',
        '{"schema_version": 1, "entries": {"k": {"block_m": "x"}}}',
        '{"schema_version": 1}',
        '[]',
    ])
    def test_wrong_schema_is_cold(self, tmp_path, payload):
        path = str(tmp_path / "cpu.json")
        with open(path, "w") as f:
            f.write(payload)
        assert AutotuneCache(path).get(KEY) is None

    def test_lookup_blocks_never_raises(self, tmp_path):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            f.write("garbage")
        set_default_cache(AutotuneCache(bad))
        assert lookup_blocks("fdsq-pallas", 8, 1024, 128, "float32", "l2",
                             k=10) is None


class TestCandidateLegality:
    def test_candidates_respect_queue_and_dim(self):
        cands = candidate_blocks(m=16, n=4096, d=100, k=1024)
        assert cands
        for c in cands:
            assert c.block_n >= 1024  # queue must fit the tile sort
            assert c.block_d <= 128  # d=100 pads to 128, never beyond

    def test_vmem_budget_filters(self):
        """The sweep keeps exactly the tiles whose kernel VMEM estimate
        fits the limit: at k=100, d=1024 the widest sort tiles do not."""
        cands = candidate_blocks(m=256, n=1 << 20, d=1024, k=100)
        assert cands
        for c in cands:
            assert knn_ops.vmem_bytes(100, 1024, *c) \
                <= knn_ops.VMEM_LIMIT_BYTES
        assert BlockShapes(256, 2048, 512) not in cands
        assert knn_ops.vmem_bytes(100, 1024, 256, 2048, 512) \
            > knn_ops.VMEM_LIMIT_BYTES

    def test_int8_widening_counted_against_vmem(self):
        """The kernel widens the int8 dataset tile to f32 in VMEM before
        the MXU dot, so int8 legality must charge 1+4 B/elem for it: this
        tile fits at 1 B/elem but not with its widened copy."""
        k, d, rf = 8, 2048, 4
        cands = candidate_blocks(m=128, n=1 << 20, d=d, k=k,
                                 rescore_factor=rf)
        bm, bn, bd = 128, 2048, 512
        need = knn_ops.vmem_bytes(k, d, bm, bn, bd, rf)
        assert need - bn * bd * 4 <= knn_ops.VMEM_LIMIT_BYTES < need
        assert BlockShapes(bm, bn, bd) not in cands
        assert cands

    @pytest.mark.parametrize("executor,k,d,rf", [
        ("fdsq-pallas", 10, 1024, None),
        ("fdsq-pallas", 100, 960, None),
        ("fdsq-pallas", 1024, 4096, None),
        ("fqsd-int8-pallas", 100, 896, 4),
    ])
    def test_every_candidate_passes_the_planner(self, executor, k, d, rf):
        """A persisted sweep winner is served, never refused: the planner
        plans every candidate the sweep offers with those very blocks."""
        m, n = 128, 1 << 20
        tier = "f32" if rf is None else "int8"
        meta = DatasetStoreMeta(padded_rows=n, padded_dim=d, n_valid=n,
                                tier=tier)
        cfg = EngineConfig(k=k, backend="pallas", rescore_factor=rf or 4)
        cands = candidate_blocks(m, n, d, k, rf)
        assert cands
        for c in cands:
            cache = AutotuneCache(path=None)
            cache.put(tuning_key(executor, m, n, d, "float32", "l2", k, rf),
                      c)
            set_default_cache(cache)
            p = plan((m, d), meta, cfg, "fqsd")
            assert p.executor == executor
            assert (p.block_m, p.block_n, p.block_d) == tuple(c)

    def test_degenerate_budget_still_returns_one(self):
        """No tile holds an int8 queue 2 x 16384 wide within VMEM: the
        sweep offers none and the tuner refuses instead of persisting a
        tile the planner would refuse."""
        assert candidate_blocks(m=1, n=1 << 16, d=128, k=4096,
                                rescore_factor=4) == []
        with pytest.raises(ValueError, match="32768-wide on-chip queue"):
            autotune_knn(8, 256, 128, k=4096, tier="int8", repeats=1)


class TestSweepAndPlanner:
    @pytest.fixture
    def engine(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((900, 32)).astype(np.float32)
        return ExactKNN(k=4, backend="pallas").fit(x)

    def test_cold_cache_falls_back_to_defaults(self, engine):
        p = engine.plan_for("fqsd", 8)
        assert (p.block_m, p.block_n, p.block_d) == (0, 0, 0)
        q = np.zeros((8, 32), np.float32)
        out = engine.query_batch(q)  # executes with kernel defaults
        assert np.asarray(out.indices).shape == (8, 4)

    def test_sweep_persists_and_planner_consults(self, tmp_path, engine):
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        set_default_cache(cache)
        p_cold = engine.plan_for("fqsd", 8)
        best, timings = autotune_knn(
            p_cold.m, p_cold.padded_rows, p_cold.padded_dim, k=engine.k,
            cache=cache, repeats=1, max_candidates=2,
        )
        assert len(timings) == 2 and all(t > 0 for t in timings.values())
        # two plans for the same key: identical tuned blocks (purity)
        p1 = engine.plan_for("fqsd", 8)
        p2 = engine.plan_for("fqsd", 8)
        assert p1 == p2
        assert (p1.block_m, p1.block_n, p1.block_d) == tuple(best) != (0, 0, 0)

    def test_tuned_plans_hit_executable_cache(self, tmp_path, engine):
        """The no-reflashing extension: after a sweep, repeated queries for
        the tuned key compile exactly once — the second call is a pure
        cache hit with zero new misses."""
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        set_default_cache(cache)
        p_cold = engine.plan_for("fqsd", 8)
        autotune_knn(p_cold.m, p_cold.padded_rows, p_cold.padded_dim,
                     k=engine.k, cache=cache, repeats=1, max_candidates=1)
        q = np.zeros((8, 32), np.float32)
        clear_executable_cache()
        engine.query_batch(q)
        first = cache_info()
        assert first["misses"] == 1
        engine.query_batch(q)
        second = cache_info()
        assert second["misses"] == first["misses"]  # no recompile
        assert second["hits"] == first["hits"] + 1

    def test_int8_sweep_uses_its_own_key(self, tmp_path):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((600, 24)).astype(np.float32)
        eng = ExactKNN(k=3, backend="pallas").fit(x).enable_int8()
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        set_default_cache(cache)
        p_cold = eng.plan_for("fqsd", 4, tier="int8")
        assert p_cold.executor == "fqsd-int8-pallas"
        best, _ = autotune_knn(
            p_cold.m, p_cold.padded_rows, p_cold.padded_dim, k=eng.k,
            tier="int8", cache=cache, repeats=1, max_candidates=1,
        )
        p = eng.plan_for("fqsd", 4, tier="int8")
        assert (p.block_m, p.block_n, p.block_d) == tuple(best)
        # the f32 plan for the same geometry is untouched (distinct key)
        pf = eng.plan_for("fqsd", 4)
        assert (pf.block_m, pf.block_n, pf.block_d) == (0, 0, 0)

    def test_rescore_factor_is_part_of_the_int8_key(self, tmp_path):
        """The rescore budget scales the int8 on-chip queue width exactly
        like k, so blocks swept at one budget must never be applied (and
        silently re-clamped past the vetted VMEM legality) under another."""
        rng = np.random.default_rng(4)
        x = rng.standard_normal((600, 24)).astype(np.float32)
        eng = ExactKNN(k=3, backend="pallas",
                       rescore_factor=4).fit(x).enable_int8()
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        set_default_cache(cache)
        p = eng.plan_for("fqsd", 4, tier="int8")
        autotune_knn(p.m, p.padded_rows, p.padded_dim, k=eng.k, tier="int8",
                     rescore_factor=4, cache=cache, repeats=1,
                     max_candidates=1)
        assert eng.plan_for("fqsd", 4, tier="int8").block_n > 0  # tuned
        other = ExactKNN(k=3, backend="pallas",
                         rescore_factor=16).fit(x).enable_int8()
        po = other.plan_for("fqsd", 4, tier="int8")
        assert (po.block_m, po.block_n, po.block_d) == (0, 0, 0)  # cold

    def test_k_is_part_of_the_key(self, tmp_path):
        """Blocks tuned at one k must not leak to plans with another k (a
        different k changes the on-chip queue width, so the stored blocks
        would be silently re-clamped by the kernel)."""
        rng = np.random.default_rng(3)
        x = rng.standard_normal((600, 24)).astype(np.float32)
        eng = ExactKNN(k=3, backend="pallas").fit(x)
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        set_default_cache(cache)
        p = eng.plan_for("fqsd", 4)
        autotune_knn(p.m, p.padded_rows, p.padded_dim, k=eng.k,
                     cache=cache, repeats=1, max_candidates=1)
        assert eng.plan_for("fqsd", 4).block_n > 0  # k=3: tuned
        other = ExactKNN(k=64, backend="pallas").fit(x)
        po = other.plan_for("fqsd", 4)
        assert (po.block_m, po.block_n, po.block_d) == (0, 0, 0)  # k=64: cold

    def test_plan_equality_and_frozen(self, engine):
        p = engine.plan_for("fqsd", 8)
        with pytest.raises(dataclasses.FrozenInstanceError):
            p.block_m = 64


PIPE_KEY = pipeline_key("fqsd-int8-streamed", m=8, n=1024, d=128,
                        dtype="float32", metric="l2", k=10)
KNOBS = PipelineKnobs(prefetch_depth=2, spec_trigger=0.5,
                      rescore_factor=4, rows_per_shard=256)


class TestPipelineEntries:
    def test_pipeline_key_format_and_bucketing(self):
        assert PIPE_KEY == "pipe|fqsd-int8-streamed|m8|n1024|d128|float32|l2|k10"
        # batch is pow2-bucketed like the kernel keys; rescore is NOT in
        # the key (it is a swept knob living in the entry value)
        assert pipeline_key("fqsd-int8-streamed", 5, 1024, 128, "float32",
                            "l2", 10) == PIPE_KEY
        assert "|r" not in PIPE_KEY

    def test_put_get_round_trip_persists(self, tmp_path):
        path = str(tmp_path / "cpu.json")
        cache = AutotuneCache(path)
        assert cache.get_pipeline(PIPE_KEY) is None
        cache.put_pipeline(PIPE_KEY, KNOBS, us_per_call=99.0)
        assert cache.get_pipeline(PIPE_KEY) == KNOBS
        assert AutotuneCache(path).get_pipeline(PIPE_KEY) == KNOBS

    def test_kinds_do_not_cross_read(self, tmp_path):
        """A block entry must never answer a pipeline lookup (or vice
        versa), even under a colliding key."""
        cache = AutotuneCache(str(tmp_path / "cpu.json"))
        cache.put(KEY, BlockShapes(32, 512, 128))
        cache.put_pipeline(PIPE_KEY, KNOBS)
        assert cache.get(PIPE_KEY) is None
        assert cache.get_pipeline(KEY) is None

    def test_load_drops_only_bad_entries(self, tmp_path):
        """Mixed-kind cache with one malformed pipe entry and one entry of
        a kind this version no longer reads: both are dropped on load, the
        good block entry survives (an older loader dropped the whole
        cache)."""
        path = str(tmp_path / "cpu.json")
        payload = {
            "schema_version": 1,
            "entries": {
                KEY: {"block_m": 32, "block_n": 512, "block_d": 128},
                PIPE_KEY: {"prefetch_depth": "not-an-int"},
                "capability|pallas": {"compiled": False},
            },
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        cache = AutotuneCache(path)
        assert cache.get(KEY) == BlockShapes(32, 512, 128)
        assert cache.get_pipeline(PIPE_KEY) is None
        assert set(cache.keys()) == {KEY}

    def test_lookup_pipeline_consults_default_cache(self):
        assert lookup_pipeline("fqsd-int8-streamed", 8, 1024, 128,
                               "float32", "l2", 10) is None
        cache = AutotuneCache(path=None)
        cache.put_pipeline(PIPE_KEY, KNOBS)
        set_default_cache(cache)
        assert lookup_pipeline("fqsd-int8-streamed", 8, 1024, 128,
                               "float32", "l2", 10) == KNOBS


class TestPipelineSweep:
    def test_sweep_persists_for_both_streamed_executors(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "dev.json"))
        best, timings = autotune_pipeline(
            m=4, n=512, d=32, k=3, cache=cache, repeats=1,
            prefetch_candidates=(1,), trigger_candidates=(0.5, 1.0),
            rescore_candidates=(2,), shard_candidates=(128,),
            directory=str(tmp_path / "shards"),
        )
        assert isinstance(best, PipelineKnobs)
        assert len(timings) == 2 and all(t > 0 for t in timings.values())
        assert best.rescore_factor == 2 and best.rows_per_shard == 128
        reread = AutotuneCache(str(tmp_path / "dev.json"))
        keys = [key for key in reread.keys() if key.startswith("pipe|")]
        assert sorted(key.split("|")[1] for key in keys) == \
            ["fqsd-int8-mmap-streamed", "fqsd-int8-streamed"]
        for key in keys:
            assert reread.get_pipeline(key) == best

    def test_non_l2_metric_rejected(self):
        with pytest.raises(ValueError, match="l2"):
            autotune_pipeline(m=4, n=512, d=32, metric="ip")


class TestExecutableCacheLRU:
    def test_eviction_bounds_size_and_counts(self):
        from repro.core import set_executable_cache_limit

        rng = np.random.default_rng(2)
        x = rng.standard_normal((700, 24)).astype(np.float32)
        q = rng.standard_normal((4, 24)).astype(np.float32)
        eng = ExactKNN(k=3, n_partitions=4).fit(x)
        clear_executable_cache()
        set_executable_cache_limit(1)
        try:
            eng.query(q)       # compile #1
            eng.query_batch(q)  # compile #2 -> evicts #1
            info = cache_info()
            assert info["size"] == 1 and info["max_entries"] == 1
            assert info["evictions"] == 1
            eng.query(q)  # evicted key recompiles
            assert cache_info()["misses"] == 3
        finally:
            set_executable_cache_limit(256)
            clear_executable_cache()

    def test_limit_validation(self):
        from repro.core import set_executable_cache_limit

        with pytest.raises(ValueError):
            set_executable_cache_limit(0)
