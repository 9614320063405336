"""Distributed exact kNN over a TPU mesh — multi-chip FQ-SD / FD-SQ.

The paper runs on one FPGA and lists "multiple FPGAs within a single system"
as future work. Here the partition axis of both dataflows becomes mesh axes:

* `fdsq_sharded` — FD-SQ scaled out. Dataset row-sharded over the WHOLE mesh
  (data x model); the incoming query (micro-batch) is replicated; every chip
  scans only its shard; per-shard queues are merged exactly by a two-stage
  hierarchical gather (model axis, then data axis). Collective volume is
  O(k) per query — independent of dataset size — which is why FD-SQ latency
  scales with chips like the paper's N parallel distance instances.

* `fqsd_sharded` — FQ-SD scaled out, small corpora. Queries shard over
  `data`, dataset shards over `model` (replicated over `data`). One merge
  stage over `model`.

* `fqsd_ring` — FQ-SD scaled out, LARGE corpora (beyond-paper optimization).
  Queries shard over `data`; dataset shards over (data x model) jointly (no
  replication — YFCC100M-scale fits: n*d*2 / 256 per chip). Dataset shards
  rotate around the `data` ring with `lax.ppermute`, and the NEXT shard's
  transfer overlaps the CURRENT shard's distance+queue work — the paper's
  host/FPGA double buffering transplanted onto the ICI torus.

All three return exact results (see tests/test_sharded_knn.py).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distance import Metric, validate_metric
from repro.core.fqsd import chunk_step
from repro.core.quantized import int8_lower_bounds
from repro.core.topk import TopK, empty_topk, sort_pairs, topk_smallest, tree_merge_sorted


def _local_scan(queries, vectors, norms, k, metric, base, chunk_rows=None):
    """Per-shard FQ-SD scan: all local rows through the local queues."""
    n = vectors.shape[0]
    chunk_rows = chunk_rows or n
    state = empty_topk((queries.shape[0],), k)
    if n % chunk_rows:
        raise ValueError(f"local rows {n} not divisible by chunk {chunk_rows}")
    c = n // chunk_rows
    if c == 1:
        return chunk_step(state, queries, vectors, norms, base, n, metric)
    chunks = vectors.reshape(c, chunk_rows, -1)
    nchunks = norms.reshape(c, chunk_rows)
    offs = jnp.arange(c, dtype=jnp.int32) * chunk_rows

    def body(st, xs):
        v, nn, off = xs
        return chunk_step(st, queries, v, nn, base + off, chunk_rows, metric), None

    state, _ = lax.scan(body, state, (chunks, nchunks, offs))
    return state


def _gather_merge(state: TopK, axis: str) -> TopK:
    """Exact merge of per-shard queues along one mesh axis (replicates)."""
    gs = lax.all_gather(state.scores, axis)  # (P, m, k)
    gi = lax.all_gather(state.indices, axis)
    return tree_merge_sorted(gs, gi)


def fdsq_sharded(
    mesh: Mesh,
    k: int,
    metric: Metric = "l2",
    data_axes: Sequence[str] = ("data", "model"),
    chunk_rows: int | None = None,
):
    """Build the distributed FD-SQ executor for `mesh`.

    Returns fn(query (m, d) replicated, dataset (N, d) row-sharded over
    data_axes, norms (N,)) -> TopK replicated. N must divide evenly over the
    product of data_axes sizes (pad via repro.core.partition first).
    """
    validate_metric(metric)
    axes = tuple(data_axes)

    def local(query, vectors, norms):
        # global base row of this shard under row-major sharding over `axes`
        base = jnp.int32(0)
        stride = vectors.shape[0]
        for ax in reversed(axes):
            base = base + lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]  # static size, version-safe
        state = _local_scan(query, vectors, norms, k, metric, base, chunk_rows)
        # hierarchical exact merge: innermost axis first (cheapest links),
        # then outer — two stages of O(k) traffic instead of one 256-way.
        for ax in reversed(axes):
            state = _gather_merge(state, ax)
        return state

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axes), P(axes)),
        out_specs=TopK(P(), P()),
        check_vma=False,
    ))


def fqsd_sharded(
    mesh: Mesh,
    k: int,
    metric: Metric = "l2",
    query_axis: str = "data",
    dataset_axis: str = "model",
    chunk_rows: int | None = None,
):
    """Distributed FQ-SD for corpora small enough to replicate over `data`.

    queries (M, d) shard over query_axis; dataset (N, d) shards over
    dataset_axis; per-query exact top-k after one merge stage.
    """
    validate_metric(metric)

    def local(queries, vectors, norms):
        base = lax.axis_index(dataset_axis) * vectors.shape[0]
        state = _local_scan(queries, vectors, norms, k, metric, base, chunk_rows)
        return _gather_merge(state, dataset_axis)

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(query_axis), P(dataset_axis), P(dataset_axis)),
        out_specs=TopK(P(query_axis), P(query_axis)),
        check_vma=False,
    ))


def fqsd_ring(
    mesh: Mesh,
    k: int,
    metric: Metric = "l2",
    query_axis: str = "data",
    model_axis: str = "model",
    chunk_rows: int | None = None,
):
    """Ring-streamed FQ-SD: fully-partitioned dataset, compute/comm overlap.

    Layout: queries P('data'); dataset rows P(('data','model')). At ring step
    s, each chip computes distances against the dataset shard currently held
    while `ppermute` ships that shard to the next chip along `data` — the
    double-buffering schedule of paper section 3.3 mapped onto the ICI torus
    (transfer of bank s+1 overlaps compute on bank s; XLA schedules the
    independent ppermute and dot concurrently since neither depends on the
    other inside one scan step).

    After D ring steps every query block has seen all (data-axis) shards of
    its model column; one merge over `model` completes the exact result.
    """
    validate_metric(metric)

    def local(queries, vectors, norms):
        d_sz = mesh.shape[query_axis]  # static size, version-safe
        t_sz = mesh.shape[model_axis]
        my_d = lax.axis_index(query_axis)
        my_t = lax.axis_index(model_axis)
        rows = vectors.shape[0]
        perm = [(i, (i + 1) % d_sz) for i in range(d_sz)]

        def body(carry, s):
            state, cur_v, cur_n = carry
            # who originally owned the shard we hold at step s
            src_row = (my_d - s) % d_sz
            base = (src_row * t_sz + my_t) * rows
            # issue the transfer of the next "bank" first, then compute on
            # the current one: independent ops => overlapped on TPU.
            nxt_v = lax.ppermute(cur_v, query_axis, perm)
            nxt_n = lax.ppermute(cur_n, query_axis, perm)
            state = chunk_step(state, queries, cur_v, cur_n, base, rows, metric)
            return (state, nxt_v, nxt_n), None

        init = empty_topk((queries.shape[0],), k)
        # unroll: the ring has a static, small trip count (= data-axis size);
        # unrolling lets XLA software-pipeline permute s+1 against compute s
        # and keeps dry-run cost analysis exact (while bodies count once).
        (state, _, _), _ = lax.scan(
            body, (init, vectors, norms), jnp.arange(d_sz, dtype=jnp.int32),
            unroll=True,
        )
        return _gather_merge(state, model_axis)

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(query_axis), P((query_axis, model_axis)), P((query_axis, model_axis))),
        out_specs=TopK(P(query_axis), P(query_axis)),
        check_vma=False,
    ))


def fqsd_ring_queries(
    mesh: Mesh,
    k: int,
    metric: Metric = "l2",
    query_axis: str = "data",
    model_axis: str | None = "model",
):
    """Query-direction ring (beyond-paper optimization of `fqsd_ring`).

    Same layout as fqsd_ring (queries P('data'), dataset P(('data','model'))),
    but the DATASET stays stationary and the (query block, running queue)
    pair rotates around the `data` ring instead. Wire bytes per step drop
    from a dataset shard (n*d/P — 6.4 GB/chip/step for YFCC) to a query
    block + queue state (m/P*(d + 2k) — ~0.4 MB/chip/step): a ~16,000x
    collective-traffic reduction at identical exact results. After D steps
    every block has visited every data row of its model column and is back
    home; one merge over `model` finishes. See EXPERIMENTS.md section Perf.

    Device memory is the stationary shard plus O(m (d + k)), where the
    dataset ring holds up to three more shards in flight (a 1M x 896 f32
    shard per v5e device compiles to 10.8 GB of temporaries there), so
    this is the ring for a corpus larger than one chip.
    ``model_axis=None`` runs it over a 1-D mesh (the Router's
    ``("data",)`` device group): the dataset rows shard over `query_axis`
    alone and the ring itself completes every query block.
    """
    validate_metric(metric)
    row_axes = (query_axis,) if model_axis is None else (query_axis, model_axis)

    def local(queries, vectors, norms):
        d_sz = mesh.shape[query_axis]  # static size, version-safe
        t_sz = 1 if model_axis is None else mesh.shape[model_axis]
        my_d = lax.axis_index(query_axis)
        my_t = 0 if model_axis is None else lax.axis_index(model_axis)
        rows = vectors.shape[0]
        base = (my_d * t_sz + my_t) * rows  # stationary local shard
        perm = [(i, (i + 1) % d_sz) for i in range(d_sz)]

        def body(carry, _):
            state, q_blk = carry
            state = chunk_step(state, q_blk, vectors, norms, base, rows, metric)
            # rotate the (queries, queue) pair to the next data row; the
            # transfer overlaps the next block's compute (independent ops).
            q_nxt = lax.ppermute(q_blk, query_axis, perm)
            s_nxt = TopK(
                lax.ppermute(state.scores, query_axis, perm),
                lax.ppermute(state.indices, query_axis, perm),
            )
            return (s_nxt, q_nxt), None

        init = empty_topk((queries.shape[0],), k)
        (state, _), _ = lax.scan(
            body, (init, queries), None, length=d_sz, unroll=True)
        # after d_sz rotations the state is back at its owner row
        if model_axis is None:
            return state
        return _gather_merge(state, model_axis)

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(query_axis), P(row_axes), P(row_axes)),
        out_specs=TopK(P(query_axis), P(query_axis)),
        check_vma=False,
    ))


def fdsq_sharded_int8(
    mesh: Mesh,
    r: int,
    data_axes: Sequence[str] = ("data", "model"),
):
    """Distributed certified-int8 first pass: the mesh analogue of
    :func:`repro.core.quantized.make_int8_bound_step`.

    Returns fn(queries (m, d) replicated, codes (N, d) int8 row-sharded over
    `data_axes`, scales/err/qnorm (N,) row-sharded) -> (lb, li) replicated
    (m, r+1) certified lower-bound queues, globally exact: every device
    computes reverse-triangle lower bounds on its local rows only (1 B/elem
    local HBM traffic), keeps its widened (m, r+1) queue, and the queues
    merge hierarchically along the mesh axes with O(r) collective volume —
    the same O(k) merge shape as :func:`fdsq_sharded`, so adding the int8
    tier costs no extra collective structure. The caller rescores the
    candidate ids in f32 and certifies exactly as on the streamed path
    (``lb[:, r]`` is the best lower bound OUTSIDE the candidate set).
    """
    if r < 1:
        raise ValueError(f"rescore budget r must be >= 1, got {r}")
    axes = tuple(data_axes)

    def local(queries, codes, scales, err, qnorm):
        base = jnp.int32(0)
        stride = codes.shape[0]
        for ax in reversed(axes):
            base = base + lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]  # static size, version-safe
        lower, idx = int8_lower_bounds(queries, codes, scales, err, qnorm,
                                       base)
        s_loc, i_loc = topk_smallest(
            lower, jnp.broadcast_to(idx[None, :], lower.shape), r + 1
        )
        state = TopK(s_loc, i_loc)
        for ax in reversed(axes):
            state = _gather_merge(state, ax)
        return state

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axes), P(axes), P(axes), P(axes)),
        out_specs=TopK(P(), P()),
        check_vma=False,
    ))


def shard_dataset(mesh: Mesh, dataset, norms, axes: Sequence[str] | str):
    """Place a padded dataset row-sharded over mesh axes."""
    spec = P(tuple(axes) if not isinstance(axes, str) else axes)
    v = jax.device_put(dataset, NamedSharding(mesh, spec))
    n = jax.device_put(norms, NamedSharding(mesh, spec))
    return v, n
