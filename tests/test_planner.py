"""Planner / executor-registry layer: purity, parity, and the no-reflashing
executable cache (paper section 3.2 made testable)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DatasetMeta,
    DatasetStoreMeta,
    EngineConfig,
    ExactKNN,
    cache_info,
    clear_executable_cache,
    largest_divisor_at_most,
    list_executors,
    plan,
)
from repro.core.planner import PLANNABLE_EXECUTORS
from repro.kernels.knn.ref import knn_ref


@pytest.fixture
def rng():
    return np.random.default_rng(0)


META = DatasetMeta(padded_rows=2048, padded_dim=128, n_valid=2000)
CFG = EngineConfig(k=10)


# ------------------------------------------------------------------ planning
class TestPlan:
    def test_deterministic_pure_data(self):
        a = plan((8, 128), META, CFG, "fqsd")
        b = plan((8, 128), META, CFG, "fqsd")
        assert a == b
        assert hash(a) == hash(b)  # frozen => usable as a cache key
        with pytest.raises(dataclasses.FrozenInstanceError):
            a.mode = "fdsq"

    def test_every_plannable_executor_is_registered(self):
        assert set(PLANNABLE_EXECUTORS) == set(list_executors())

    @pytest.mark.parametrize("mode,executor", [
        ("fdsq", "fdsq-xla"), ("fqsd", "fqsd-xla"),
    ])
    def test_xla_routing(self, mode, executor):
        p = plan((4, 128), META, CFG, mode)
        assert p.executor == executor and p.mode == mode

    def test_pallas_serves_both_modes_with_one_executor(self):
        cfg = dataclasses.replace(CFG, backend="pallas")
        lat = plan((1, 128), META, cfg, "fdsq")
        thr = plan((64, 128), META, cfg, "fqsd")
        assert lat.executor == thr.executor == "fdsq-pallas"
        assert (lat.mode, thr.mode) == ("fdsq", "fqsd")

    def test_pallas_serves_cos_fused(self):
        # cos used to fall back to the XLA executors; the fused kernel now
        # serves it via pre-normalized rows through the ip epilogue
        cfg = dataclasses.replace(CFG, backend="pallas", metric="cos")
        assert plan((1, 128), META, cfg, "fdsq").executor == "fdsq-pallas"

    def test_pallas_int8_tier_routes_to_fused_quantized(self):
        from repro.core import DatasetStoreMeta

        meta = DatasetStoreMeta(padded_rows=2048, padded_dim=128,
                                n_valid=2000, tier="int8")
        cfg = dataclasses.replace(CFG, backend="pallas")
        p = plan((8, 128), meta, cfg, "fqsd")
        assert p.executor == "fqsd-int8-pallas" and p.mode == "fqsd-int8"
        assert p.tier == "int8"
        # same storage tier without the pallas backend keeps the XLA scan
        p_xla = plan((8, 128), meta, CFG, "fqsd")
        assert p_xla.executor == "fqsd-int8"

    def test_sharded_routing(self):
        meta = dataclasses.replace(META, sharded=True)
        assert plan((1, 128), meta, CFG, "fdsq").executor == "fdsq-sharded"
        p = plan((8, 128), meta, CFG, "fqsd")
        assert p.executor == "fqsd-sharded" and p.mode == "fqsd-sharded"

    def test_chunk_is_a_real_divisor(self):
        # padded rows with an odd factor: halving 8192 never reaches a
        # divisor > 128, the gcd-style planner must find 1152/384/...
        meta = DatasetMeta(padded_rows=1152, padded_dim=128, n_valid=1000)
        p = plan((8, 128), meta, dataclasses.replace(CFG, chunk_rows=500), "fqsd")
        assert p.chunk_rows > 0 and meta.padded_rows % p.chunk_rows == 0
        assert p.chunk_rows == 384

    def test_fdsq_partitions_divide_rows(self):
        meta = DatasetMeta(padded_rows=1152, padded_dim=128, n_valid=1000)
        p = plan((1, 128), meta, dataclasses.replace(CFG, n_partitions=7), "fdsq")
        assert p.n_partitions > 0 and meta.padded_rows % p.n_partitions == 0


class TestPlanKwargValidation:
    """Regression (ISSUE 4 satellite): plan()/plan_for() must reject
    unknown kwargs loudly, naming the offending key — a typo'd option must
    fail the call, not silently plan something else."""

    def test_plan_rejects_unknown_kwargs_with_key_name(self):
        with pytest.raises(TypeError, match="stream_rowz"):
            plan((8, 128), META, CFG, "fqsd", stream_rowz=512)

    def test_plan_names_every_offending_key(self):
        with pytest.raises(TypeError, match="(?s)chunk.*tierz"):
            plan((8, 128), META, CFG, "fqsd", tierz="int8", chunk=64)

    def test_plan_for_rejects_unknown_kwargs(self, rng):
        x = rng.standard_normal((300, 16)).astype(np.float32)
        eng = ExactKNN(k=3).fit(x)
        with pytest.raises(TypeError, match="deadline"):
            eng.plan_for("fqsd", 8, deadline=5.0)


class TestPerRequestPlanOverrides:
    """The request-first API threads per-request k/metric through plan();
    they land on the plan AND its cache_key, so per-request values hit
    exactly the executables a dedicated engine would have compiled."""

    def test_k_and_metric_override_config(self):
        p = plan((8, 128), META, CFG, "fqsd", k=3, metric="ip")
        assert (p.k, p.metric) == (3, "ip")
        base = plan((8, 128), META, CFG, "fqsd")
        assert (base.k, base.metric) == (10, "l2")
        assert p.cache_key() != base.cache_key()

    def test_override_equals_dedicated_config(self):
        import dataclasses as dc

        dedicated = plan((8, 128), META, dc.replace(CFG, k=3, metric="ip"),
                         "fqsd")
        assert plan((8, 128), META, CFG, "fqsd", k=3, metric="ip") == dedicated

    def test_invalid_override_rejected(self):
        with pytest.raises(ValueError, match="k must be >= 1"):
            plan((8, 128), META, CFG, "fqsd", k=0)


class TestPipelineKnobsOnPlan:
    """ISSUE 6 tentpole: tuned pipeline knobs land on streamed-int8 plans
    and ride the plan cache key (tuned vs untuned plans must never collide
    in any plan-keyed cache)."""

    STREAM_META = DatasetStoreMeta(padded_rows=2048, padded_dim=128,
                                   n_valid=2000, tier="int8", resident=False,
                                   n_shards=4, rows_per_shard=512)

    def _tune(self, executor="fqsd-int8-streamed", **kw):
        from repro.tuning import (AutotuneCache, PipelineKnobs, pipeline_key,
                                  set_default_cache)

        knobs = PipelineKnobs(prefetch_depth=kw.get("prefetch_depth", 4),
                              spec_trigger=kw.get("spec_trigger", 0.25),
                              rescore_factor=kw.get("rescore_factor", 8),
                              rows_per_shard=512)
        cache = AutotuneCache(path=None)
        cache.put_pipeline(pipeline_key(executor, 8, 2048, 128, "float32",
                                        "l2", 10), knobs)
        set_default_cache(cache)
        return knobs

    def test_untuned_plan_carries_sentinels(self):
        p = plan((8, 128), self.STREAM_META, CFG, "fqsd")
        assert p.executor == "fqsd-int8-streamed"
        assert (p.prefetch_depth, p.spec_trigger) == (0, -1.0)
        assert p.rescore_factor == CFG.rescore_factor

    def test_tuned_knobs_land_on_plan_and_cache_key(self):
        untuned = plan((8, 128), self.STREAM_META, CFG, "fqsd")
        knobs = self._tune()
        tuned = plan((8, 128), self.STREAM_META, CFG, "fqsd")
        assert tuned.prefetch_depth == knobs.prefetch_depth
        assert tuned.spec_trigger == knobs.spec_trigger
        assert tuned.rescore_factor == knobs.rescore_factor
        assert tuned.cache_key() != untuned.cache_key()

    def test_pinned_rescore_budget_wins_over_tuner(self):
        self._tune(rescore_factor=8)
        cfg = dataclasses.replace(CFG, rescore_factor=2, rescore_pinned=True)
        p = plan((8, 128), self.STREAM_META, cfg, "fqsd")
        assert p.rescore_factor == 2  # pinned by the caller
        # prefetch/trigger are pure scheduling, they still apply
        assert (p.prefetch_depth, p.spec_trigger) == (4, 0.25)

    def test_resident_plans_never_carry_pipeline_knobs(self):
        self._tune()
        p = plan((8, 128), META, CFG, "fqsd")
        assert (p.prefetch_depth, p.spec_trigger) == (0, -1.0)


class TestLargestDivisor:
    @pytest.mark.parametrize("n,cap,want", [
        (16384, 3000, 2048),   # old loop would halve down to 1
        (1152, 500, 384),
        (1152, 1152, 1152),
        (1152, 10_000, 1152),  # cap beyond n clamps to n
        (7, 3, 1),             # prime: only 1 divides below cap
        (100, 1, 1),
    ])
    def test_values(self, n, cap, want):
        assert largest_divisor_at_most(n, cap) == want

    def test_cap_below_one_is_safe(self):
        # the old while-loop spun / returned 0 here; must now be clamped
        assert largest_divisor_at_most(1024, 0) == 1
        assert largest_divisor_at_most(1024, -5) == 1

    def test_invalid_n(self):
        with pytest.raises(ValueError):
            largest_divisor_at_most(0, 4)


def test_engine_chunk_regression(rng):
    """Non-power-of-two padded rows + odd chunk request: the old
    `while rows % chunk: chunk //= 2` loop degraded to a per-row scan
    (or hung for chunk<=0); the planner must pick a real divisor and the
    results must stay exact."""
    x = rng.standard_normal((1000, 40)).astype(np.float32)
    q = rng.standard_normal((5, 40)).astype(np.float32)
    eng = ExactKNN(k=7, chunk_rows=500, n_partitions=3).fit(x)  # rows pad to 1152
    out = eng.query_batch(q)
    p = eng.plans[-1]
    assert p.padded_rows % p.chunk_rows == 0 and p.chunk_rows >= 128
    ref_s, _ = knn_ref(jnp.asarray(q), jnp.asarray(x), 7)
    np.testing.assert_allclose(np.asarray(out.scores), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------- executor parity
def _ref(q, x, k, metric="l2"):
    return knn_ref(jnp.asarray(q), jnp.asarray(x), k, metric)


class TestExecutorParity:
    """Every registered executor must agree with kernels/knn/ref.py."""

    M, N, D, K = 6, 700, 33, 5

    @pytest.fixture
    def data(self, rng):
        x = rng.standard_normal((self.N, self.D)).astype(np.float32)
        q = rng.standard_normal((self.M, self.D)).astype(np.float32)
        return q, x

    def _check(self, eng, q, x, call):
        out = call(eng)
        ref_s, _ = _ref(q, x, self.K)
        np.testing.assert_allclose(np.asarray(out.scores), np.asarray(ref_s),
                                   rtol=1e-5, atol=1e-4)

    def test_fdsq_xla(self, data):
        q, x = data
        eng = ExactKNN(k=self.K, n_partitions=4).fit(x)
        self._check(eng, q, x, lambda e: e.query(q))
        assert eng.plans[-1].executor == "fdsq-xla"

    def test_fqsd_xla(self, data):
        q, x = data
        eng = ExactKNN(k=self.K, chunk_rows=256).fit(x)
        self._check(eng, q, x, lambda e: e.query_batch(q))
        assert eng.plans[-1].executor == "fqsd-xla"

    def test_fdsq_pallas(self, data):
        q, x = data
        eng = ExactKNN(k=self.K, backend="pallas").fit(x)
        self._check(eng, q, x, lambda e: e.query(q))
        self._check(eng, q, x, lambda e: e.query_batch(q))
        assert {p.executor for p in eng.plans} == {"fdsq-pallas"}

    def test_fqsd_streamed(self, data):
        q, x = data
        eng = ExactKNN(k=self.K).fit(x)
        self._check(eng, q, x, lambda e: e.search_streamed(q, x, rows_per_partition=256))
        assert eng.plans[-1].executor == "fqsd-streamed"

    def test_sharded_executors_trivial_mesh(self, data):
        """1x1 mesh exercises the shard_map executors on a single device;
        multi-device exactness is covered by tests/sharded_check.py."""
        q, x = data
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        eng = ExactKNN(k=self.K, mesh=mesh).fit(x)
        self._check(eng, q, x, lambda e: e.query(q))
        self._check(eng, q, x, lambda e: e.query_batch(q))
        assert [p.executor for p in eng.plans] == ["fdsq-sharded", "fqsd-sharded"]


# ---------------------------------------------------- no-reflashing cache
class TestExecutableCache:
    def test_mode_switch_reuses_executables(self, rng):
        """FD-SQ <-> FQ-SD flips on already-seen shapes must be pure cache
        hits — the paper's 'switching logical configurations never
        reflashes the chip'."""
        x = rng.standard_normal((1500, 48)).astype(np.float32)
        q = rng.standard_normal((8, 48)).astype(np.float32)
        eng = ExactKNN(k=4).fit(x)
        clear_executable_cache()
        eng.query(q)
        eng.query_batch(q)
        after_first = cache_info()
        assert after_first["misses"] == 2  # one compile per logical config
        for _ in range(3):  # six switches on seen shapes
            eng.query(q)
            eng.query_batch(q)
        after = cache_info()
        assert after["misses"] == after_first["misses"]  # no recompile
        assert after["hits"] == after_first["hits"] + 6
        assert after["size"] == after_first["size"]

    def test_new_shape_compiles_once(self, rng):
        x = rng.standard_normal((1500, 48)).astype(np.float32)
        eng = ExactKNN(k=4).fit(x)
        clear_executable_cache()
        q1 = rng.standard_normal((8, 48)).astype(np.float32)
        q2 = rng.standard_normal((16, 48)).astype(np.float32)
        eng.query(q1)
        eng.query(q2)  # new batch shape -> one more executable
        eng.query(q1)
        eng.query(q2)
        info = cache_info()
        assert info["misses"] == 2 and info["hits"] == 2
