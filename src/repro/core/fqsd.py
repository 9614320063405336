"""FQ-SD — Fixed Queries, Streamed Dataset (throughput-optimized; paper fig. 1).

A fixed batch of M queries is resident; the dataset flows through in equal
padded partitions. Each partition step computes an (M, chunk) score tile and
inserts it into the M running kNN queues. Distances never materialize beyond
one tile — exactly the FPGA dataflow where distance pipelines feed queues
directly.

Two tiers, matching the paper's memory hierarchy:

* `fqsd_scan`     — the dataset (already in HBM) is consumed chunk-by-chunk
                    with `lax.scan`; chunking bounds the score-tile footprint
                    (an (M, N) matrix for GIST would be 4 GB).
* `fqsd_streamed` — the dataset does NOT fit in device memory: a host
                    iterator of partitions is consumed through the
                    double-buffered streamer; each partition is processed by
                    one compiled step function (same executable every
                    partition = the fixed bitstream).
"""
from __future__ import annotations

import functools
from typing import Iterable

import jax
import jax.numpy as jnp

from repro.core import partition as part
from repro.core.distance import Metric, pairwise_scores, validate_metric
from repro.core.topk import TopK, empty_topk, merge_topk, sort_pairs


def _masked_scores(
    queries: jax.Array,
    chunk: jax.Array,
    chunk_norms: jax.Array | None,
    n_valid: jax.Array | int,
    metric: Metric,
) -> jax.Array:
    """Score tile with padded rows forced to +inf (can never enter a queue).

    Validity is carried by BOTH the n_valid count and +inf norms: the norm
    channel poisons L2 scores arithmetically, but for ip/cos a zero-padded
    row scores 0/1, so the explicit mask (norm finiteness) is authoritative
    for every metric.
    """
    s = pairwise_scores(queries, chunk, metric, x_norms=chunk_norms)
    n = chunk.shape[0]
    mask = jnp.arange(n, dtype=jnp.int32) < n_valid
    if chunk_norms is not None:
        mask = mask & jnp.isfinite(chunk_norms)
    return jnp.where(mask[None, :], s, jnp.inf)


def chunk_step(
    state: TopK,
    queries: jax.Array,
    chunk: jax.Array,
    chunk_norms: jax.Array | None,
    base_index: jax.Array | int,
    n_valid: jax.Array | int,
    metric: Metric = "l2",
) -> TopK:
    """Insert one dataset partition into the running queues (exact).

    The two stages carry stable scope names in the compiled program's op
    metadata: ``knn.distance_tile`` (the masked (M, chunk) score tile) and
    ``knn.chunk_merge`` (its merge into the k running queues)."""
    with jax.named_scope("knn.distance_tile"):
        s = _masked_scores(queries, chunk, chunk_norms, n_valid, metric)
    with jax.named_scope("knn.chunk_merge"):
        idx = base_index + jnp.arange(chunk.shape[0], dtype=jnp.int32)
        idx = jnp.broadcast_to(idx[None, :], s.shape)
        return merge_topk(state, s, idx)


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk_rows"))
def fqsd_scan(
    queries: jax.Array,
    dataset: jax.Array,
    dataset_norms: jax.Array,
    k: int,
    metric: Metric = "l2",
    chunk_rows: int = 8192,
) -> TopK:
    """Exact kNN of M resident queries over an HBM-resident dataset.

    dataset : (N, d) padded per `repro.core.partition`; dataset_norms carry
    +inf on padded rows. N must be a multiple of chunk_rows (pad first).
    """
    validate_metric(metric)
    n, d = dataset.shape
    if n % chunk_rows:
        raise ValueError(f"N={n} not a multiple of chunk_rows={chunk_rows}")
    c = n // chunk_rows
    chunks = dataset.reshape(c, chunk_rows, d)
    norm_chunks = dataset_norms.reshape(c, chunk_rows)
    bases = (jnp.arange(c, dtype=jnp.int32) * chunk_rows)

    def body(state: TopK, xs):
        chunk, norms, base = xs
        new = chunk_step(state, queries, chunk, norms, base, chunk_rows, metric)
        return new, None

    init = empty_topk((queries.shape[0],), k)
    final, _ = jax.lax.scan(body, init, (chunks, norm_chunks, bases))
    return final


def make_partition_step(k: int, metric: Metric = "l2"):
    """Compile-once step for host-streamed partitions (the fixed bitstream).

    Returns a jit'd fn(state, queries, vectors, norms, base_index, n_valid).
    All partitions share one padded shape, so this compiles exactly once.
    """
    validate_metric(metric)

    @jax.jit
    def step(state: TopK, queries, vectors, norms, base_index, n_valid) -> TopK:
        return chunk_step(state, queries, vectors, norms, base_index, n_valid, metric)

    return step


def fqsd_streamed(
    queries: jax.Array,
    partitions: Iterable[part.PaddedDataset],
    k: int,
    metric: Metric = "l2",
    prefetch_depth: int = 2,
    put_fn=None,
    step_fn=None,
    stream_stats: dict | None = None,
    put_retries: int = 0,
    retry_backoff_s: float = 0.05,
    health: dict | None = None,
) -> TopK:
    """Exact kNN over a host-resident dataset streamed with double buffering.

    `partitions` is typically `partition.iter_partitions(...)`; every yielded
    partition has the same padded shape. The streamer keeps one partition in
    flight (two banks); the step executable is reused across partitions.
    `step_fn` lets callers inject an already-built step (the executor layer
    caches it per plan so repeated streamed searches share one executable).
    A `stream_stats` dict receives the streamer's transfers/restarts
    counters (serving observability). `put_retries`/`retry_backoff_s`/
    `health` ride through to the streamer's bounded device_put retry
    (shard-*read* resilience belongs to the partition source, e.g.
    ``streaming.ResilientShardSource``).
    """
    from repro.core.streaming import DoubleBufferedStream, device_put_partition

    step = step_fn if step_fn is not None else make_partition_step(k, metric)
    state = empty_topk((queries.shape[0],), k)

    stream = DoubleBufferedStream(
        partitions, depth=prefetch_depth,
        put_fn=put_fn if put_fn is not None else device_put_partition,
        put_retries=put_retries, retry_backoff_s=retry_backoff_s,
        health=health,
    )
    for p in stream:
        state = step(
            state,
            queries,
            p.vectors,
            p.norms,
            jnp.int32(p.base_index),
            jnp.int32(p.n_valid),
        )
    if stream_stats is not None:
        stream_stats["transfers"] = stream.transfers
        stream_stats["restarts"] = stream.restarts
    return state


def make_direct_partition_step(k: int):
    """Compile-once streamed step in the DIRECT ``(q - x)^2`` form.

    The streamed analogue of ``kernels.knn.ops.knn_exact_direct``: one
    partition's literal f32 sums of squared differences merged into the
    running (m, k) state by a full lexicographic (value, index) sort —
    chunk- and order-invariant, so a shard-by-shard scan equals a full-sort
    oracle bit for bit. This is the exactness oracle AND uncertified-row
    fallback for the streamed int8 executors (their candidate rescore uses
    the identical formula, which is what makes certified rows bitwise equal
    to this oracle). Validity (padding / tombstones / filter masks) rides
    the norms channel: non-finite norm => +inf score and index -1.

    Returns a jit'd fn(s, i, queries, vectors, norms, base) -> (s, i).
    """

    @jax.jit
    def step(s, i, queries, vectors, norms, base):
        n = vectors.shape[0]
        q32 = queries.astype(jnp.float32)
        diff = q32[:, None, :] - vectors[None, :, :].astype(jnp.float32)
        d = jnp.sum(diff * diff, axis=-1)
        valid = jnp.isfinite(norms)
        d = jnp.where(valid[None, :], d, jnp.inf)
        idx = jnp.where(valid, base + jnp.arange(n, dtype=jnp.int32),
                        jnp.int32(-1))
        s_all = jnp.concatenate([s, d], axis=-1)
        i_all = jnp.concatenate(
            [i, jnp.broadcast_to(idx[None, :], d.shape)], axis=-1
        )
        s2, i2 = sort_pairs(s_all, i_all)
        return s2[:, :k], i2[:, :k]

    return step


def streamed_direct_scan(
    queries: jax.Array,
    partitions: Iterable[part.PaddedDataset],
    k: int,
    prefetch_depth: int = 2,
    step_fn=None,
    stream_stats: dict | None = None,
    put_retries: int = 0,
    retry_backoff_s: float = 0.05,
    health: dict | None = None,
) -> TopK:
    """Exact direct-form kNN over streamed partitions (l2 only).

    The streamed f32 oracle: double-buffered like :func:`fqsd_streamed`,
    but scoring through :func:`make_direct_partition_step`, so the result
    is bit-identical to a full lexicographic sort of every (q - x)^2
    distance — the reference the streamed int8 executors are tested
    against and fall back to for uncertified queries. Retry/health knobs
    mirror :func:`fqsd_streamed`.
    """
    from repro.core.streaming import DoubleBufferedStream, device_put_partition

    step = step_fn if step_fn is not None else make_direct_partition_step(k)
    m = queries.shape[0]
    s = jnp.full((m, k), jnp.inf, jnp.float32)
    i = jnp.full((m, k), -1, jnp.int32)
    stream = DoubleBufferedStream(partitions, depth=prefetch_depth,
                                  put_fn=device_put_partition,
                                  put_retries=put_retries,
                                  retry_backoff_s=retry_backoff_s,
                                  health=health)
    for p in stream:
        s, i = step(s, i, queries, p.vectors, p.norms, jnp.int32(p.base_index))
    if stream_stats is not None:
        stream_stats["transfers"] = stream.transfers
        stream_stats["restarts"] = stream.restarts
    return TopK(s, jnp.where(jnp.isfinite(s), i, -1))
