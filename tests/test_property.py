"""Property-based tests (hypothesis) for system invariants.

Invariants of the kNN queue semantics (paper section 3.3):
  P1  scores returned are exactly the k smallest of the score matrix row
  P2  results are sorted ascending; ties broken by smaller index
  P3  merge is associative/commutative & order-invariant: any partitioning of
      the dataset (FQ-SD chunking, FD-SQ partitions, mesh shards) gives the
      same queue state
  P4  every returned index is valid (in range or -1 iff fewer than k rows)
  P5  engine invariance: query_batch == row-wise query == streamed search
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (
    empty_topk,
    knn_oracle,
    merge_topk,
    pairwise_scores,
    topk_smallest,
    tree_merge_sorted,
)

f32 = np.float32


def _ftz(a):
    """Flush f32 subnormals to zero, as XLA does on CPU and TPU (see
    repro.core.topk): the order the kernels see."""
    a = np.asarray(a, f32)
    return np.where(np.abs(a) < np.finfo(f32).tiny, f32(0), a)


def _scores(draw, m, n):
    # values with repeats to exercise tie handling
    base = draw(st.lists(
        st.floats(-100, 100, allow_nan=False, width=32), min_size=m * n, max_size=m * n
    ))
    return np.asarray(base, f32).reshape(m, n)


@st.composite
def score_matrix(draw):
    m = draw(st.integers(1, 5))
    n = draw(st.integers(1, 64))
    k = draw(st.integers(1, 12))
    s = _scores(draw, m, n)
    if draw(st.booleans()):  # inject exact ties
        s = np.round(s)
    return s, k


@given(score_matrix())
@settings(max_examples=60, deadline=None)
def test_p1_p2_topk_exact_sorted(case):
    s, k = case
    m, n = s.shape
    idx = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n))
    got_s, got_i = topk_smallest(jnp.asarray(s), jnp.asarray(idx), k)
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    kk = min(k, n)
    # P1 exact (no fp ops) up to flush-to-zero: subnormals order as zero
    got_s = _ftz(got_s)
    ref = np.sort(_ftz(s), axis=1)[:, :kk]
    np.testing.assert_array_equal(got_s[:, :kk], ref)
    assert (np.diff(got_s[:, :kk], axis=1) >= 0).all()  # P2 sorted (inf-inf=nan in pad)
    # P2 tie order: within equal scores indices ascend
    for r in range(m):
        for j in range(kk - 1):
            if got_s[r, j] == got_s[r, j + 1]:
                assert got_i[r, j] < got_i[r, j + 1]
    # P4 validity
    assert ((got_i[:, :kk] >= 0) & (got_i[:, :kk] < n)).all()
    if k > n:
        assert (got_i[:, n:] == -1).all() and np.isinf(got_s[:, n:]).all()


@given(score_matrix(), st.integers(1, 7))
@settings(max_examples=40, deadline=None)
def test_p3_chunking_invariance(case, n_chunks):
    s, k = case
    m, n = s.shape
    idx = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n)).copy()
    ref_s, _ = topk_smallest(jnp.asarray(s), jnp.asarray(idx), k)
    # feed the same candidates through the queue in n_chunks pieces
    state = empty_topk((m,), k)
    bounds = np.linspace(0, n, n_chunks + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        if a == b:
            continue
        state = merge_topk(state, jnp.asarray(s[:, a:b]), jnp.asarray(idx[:, a:b]))
    np.testing.assert_array_equal(_ftz(state.scores), _ftz(ref_s))


@given(score_matrix(), st.integers(2, 5))
@settings(max_examples=30, deadline=None)
def test_p3_tree_merge_equals_serial(case, p):
    s, k = case
    m, n = s.shape
    idx = np.broadcast_to(np.arange(n, dtype=np.int32), (m, n)).copy()
    ref_s, _ = topk_smallest(jnp.asarray(s), jnp.asarray(idx), k)
    # split columns into p local queues then tree-merge
    locals_s, locals_i = [], []
    bounds = np.linspace(0, n, p + 1).astype(int)
    for a, b in zip(bounds[:-1], bounds[1:]):
        ls, li = topk_smallest(
            jnp.asarray(s[:, a:b]) if b > a else jnp.full((m, 1), np.inf, f32),
            jnp.asarray(idx[:, a:b]) if b > a else jnp.full((m, 1), -1, np.int32),
            k,
        )
        locals_s.append(ls); locals_i.append(li)
    merged = tree_merge_sorted(jnp.stack(locals_s), jnp.stack(locals_i))
    np.testing.assert_array_equal(_ftz(merged.scores), _ftz(ref_s))


@given(st.integers(1, 4), st.integers(5, 40), st.integers(2, 16), st.integers(1, 6),
       st.sampled_from(["l2", "ip", "cos"]), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_p5_engine_paths_agree(m, n, d, k, metric, seed):
    from repro.core import ExactKNN

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(f32)
    q = rng.standard_normal((m, d)).astype(f32)
    eng = ExactKNN(k=k, metric=metric, n_partitions=2, chunk_rows=128).fit(x)
    batch = eng.query_batch(q)
    ref_s, _ = knn_oracle(pairwise_scores(jnp.asarray(q), jnp.asarray(x), metric), k)
    np.testing.assert_allclose(
        np.asarray(batch.scores), np.asarray(ref_s), rtol=1e-5, atol=1e-5
    )
    single = eng.query(q[0])
    np.testing.assert_allclose(
        np.asarray(single.scores[0]), np.asarray(batch.scores[0]), rtol=1e-6, atol=1e-6
    )


@given(st.integers(1, 4), st.integers(140, 520), st.integers(4, 33),
       st.integers(1, 5), st.floats(0.0, 1.0), st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_p6_speculation_trigger_is_result_invariant(m, n, d, k, trigger, seed):
    """P6 (ISSUE 6): the streamed int8 executor returns bit-identical top-k
    (values, indices, tie order) to the streamed f32 direct-form oracle at
    EVERY speculation trigger point — the trigger only reschedules reads."""
    from repro.api import SearchRequest
    from repro.core import ExactKNN
    from repro.core.fqsd import streamed_direct_scan
    from repro.store import DatasetStore

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(f32)
    q = rng.standard_normal((m, d)).astype(f32)
    store = DatasetStore.from_array(x, rows_per_shard=128)
    eng = ExactKNN(k=k, device_budget_bytes=1).fit_store(store)
    eng.enable_int8()
    res = eng.search(SearchRequest(queries=q, tier="int8",
                                   spec_trigger=trigger))
    assert res.plan.executor == "fqsd-int8-streamed"
    oracle = streamed_direct_scan(eng._pad_queries(q),
                                  eng.store.shard_source("f32"), eng.k)
    np.testing.assert_array_equal(np.asarray(res.topk.scores),
                                  np.asarray(oracle.scores))
    np.testing.assert_array_equal(np.asarray(res.topk.indices),
                                  np.asarray(oracle.indices))
