"""Int8-quantized distance scan with exact rescoring (paper section 5 Future Work).

The paper names quantization as the lever to raise FQ-SD throughput, at the
cost of approximation. We implement it so the final answer remains EXACT:

1. Symmetric per-vector int8 quantization: x ~= s_x * q_x, q_x in [-127,127].
2. The scan computes approximate squared-L2 on int8 via one int8xint8->int32
   MXU GEMM (4x less HBM traffic than f32 — the FQ-SD bottleneck is memory
   bandwidth, see EXPERIMENTS.md roofline).
3. Per-pair error bound: for x = s_x q_x + e_x the scan computes
   d_hat = ||q - x_hat||^2 EXACTLY (the quantized norm ||x_hat||^2 is
   stored, not approximated), then brackets the true distance with the
   reverse-triangle bound below.
4. Candidate filter: keep every row whose LOWER bound is <= the k-th smallest
   UPPER bound; rescore candidates in f32; take exact top-k. A boolean
   certificate (`exact`) reports whether the static rescore budget covered
   the candidate set — on all tested real-scale distributions a 4x budget
   certifies exactness (property-tested).

Bound derivation (squared L2): d(q,x) = ||q - x||^2 with x = x_hat + e,
x_hat = s_x q_x, and d_hat = ||q - x_hat||^2 computed exactly from the
stored quantized norm ||x_hat||^2 = s_x^2 ||q_x||^2:
  sqrt(d) = ||(q - x_hat) - e||  =>  |sqrt(d) - sqrt(d_hat)| <= ||e||
  =>  max(sqrt(d_hat) - err_x, 0)^2 <= d <= (sqrt(d_hat) + err_x)^2
with err_x >= ||e_x|| the stored per-row error norm. The quantized norm
must be exact: substituting ||x||^2 - err^2 for it drops the cross term
2<x_hat, e>, which reaches 2*||x||*err when the quantization error aligns
with the row direction — lower bounds then overshoot true distances and
the filter silently prunes true neighbors while still certifying.

The bracket is sound in real arithmetic; d_hat itself is evaluated in f32
via the cancellation form qn - 2<q,x_hat> + ||x_hat||^2, so the certified
claim (like every exactness claim in this repo, including the oracle) is
modulo f32 rounding of order ||q||^2 * 2^-24 — the same precision class
as the f32 scans it certifies against, not a structural bound violation.
That holds only because every contraction pins ``EXACT_PRECISION``
(HIGHEST): a TPU's default precision rounds the f32 query through bf16,
an error of order 2^-8 that the bound does not cover.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance import EXACT_PRECISION
from repro.core.topk import TopK, sort_pairs, topk_smallest


class QuantizedDataset(NamedTuple):
    q: jax.Array  # (N, d) int8
    scales: jax.Array  # (N,) f32
    err: jax.Array  # (N,) f32 — certified ||e_x|| upper bound
    norms_sq: jax.Array  # (N,) f32 — EXACT f32 row norms: the validity
    #                      channel. +inf marks an invalid row (padding /
    #                      tombstone): masked out of bounds, candidates,
    #                      and rescore.
    qnorm_sq: jax.Array  # (N,) f32 — EXACT quantized norm ||x_hat||^2 =
    #                      s_x^2 * ||q_x||^2. Must be this exact value (not
    #                      derived from norms_sq) or the distance bounds
    #                      lose soundness — see module docstring.


class Int8Partition(NamedTuple):
    """One streamed shard of the int8 tier — the multi-array partition the
    double-buffered streamer ships in one prefetch slot (1 B/element codes
    plus 12 B/row of f32 side channels instead of 4 B/element f32 rows).

    ``qnorm`` is the EXACT quantized norm ``||x_hat||^2`` with validity
    already folded in: +inf on padding / tombstones / filter-masked rows
    (the producer folds its ``norms_sq`` mask here so the scan step needs a
    single channel). ``n_valid``/``base_index`` stay host scalars.
    """

    q: jax.Array  # (padded_rows, padded_dim) int8 codes
    scales: jax.Array  # (padded_rows,) f32
    err: jax.Array  # (padded_rows,) f32 — certified ||e_x|| upper bound
    qnorm: jax.Array  # (padded_rows,) f32 — exact ||x_hat||^2; +inf invalid
    n_valid: int
    base_index: int

    def scan_bytes(self) -> int:
        """Bytes one streamed pass moves for this shard: int8 codes plus
        the three per-row f32 channels (scales, err, qnorm)."""
        rows = int(self.q.shape[0])
        return rows * int(self.q.shape[1]) + 12 * rows


def int8_lower_bounds(queries, codes, scales, err, qnorm, base):
    """Certified lower bounds of one int8 shard against a query batch.

    Returns ``(lower, idx)`` with ``lower`` the (m, n) reverse-triangle
    lower bounds (+inf on invalid rows, i.e. non-finite ``qnorm``) and
    ``idx`` the (n,) global row ids (-1 on invalid rows). This is the one
    formula both the streamed step (:func:`make_int8_bound_step`) and the
    mesh-sharded local scan trace, so every int8 executor prunes with
    bitwise-identical bounds.
    """
    n = codes.shape[0]
    q32 = queries.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    # (M, d) f32 x (N, d) i8 -> f32: dataset-side HBM traffic stays
    # 1 B/element (same contraction as _approx_l2)
    cross = jax.lax.dot_general(
        q32, codes.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT_PRECISION,
    ) * scales[None, :]
    d_hat = jnp.maximum(qn - 2.0 * cross + qnorm[None, :], 0.0)
    valid = jnp.isfinite(qnorm)
    root = jnp.sqrt(d_hat)
    lower = jnp.where(valid[None, :],
                      jnp.maximum(root - err[None, :], 0.0) ** 2, jnp.inf)
    idx = jnp.where(valid, base + jnp.arange(n, dtype=jnp.int32),
                    jnp.int32(-1))
    return lower, idx


def make_int8_bound_step(r: int):
    """Compile-once step for the *streamed* quantized scan: insert one int8
    shard's certified lower bounds into the running (m, r+1) candidate queue.

    The queue is one entry wider than the rescore budget ``r`` so the
    epilogue can read the smallest lower bound OUTSIDE the candidate set
    (entry r) for the exactness certificate. Invalid rows (+inf ``qnorm``)
    get index -1, so a padded tail row of the final shard can never leak a
    global id that collides with the delta-row id space.

    Shard-local selection runs through ``topk_smallest`` (O(n) lax.top_k,
    not a full sort — the per-shard sort would dominate the whole streamed
    scan) and only the selected 2(r+1) entries merge lexicographically.
    top_k's selection among EQUAL lower bounds straddling the queue
    boundary is index-arbitrary, and that is sound here: dropping a tying
    row can only replace a queue entry with an equal *value*, so the
    certificate's threshold entry lb[r] is unchanged — and any query whose
    true neighbor could hide behind such a tie necessarily fails the
    strict ``lb[r] > kth-exact`` certificate and takes the exact streamed
    fallback. Certified results therefore stay bit-identical to the
    full-sort oracle.

    Returns a jit'd fn(lb, li, queries, codes, scales, err, qnorm, base)
    -> (lb, li); all shards share one padded shape, so this compiles once.
    """
    if r < 1:
        raise ValueError(f"rescore budget r must be >= 1, got {r}")

    @jax.jit
    def step(lb, li, queries, codes, scales, err, qnorm, base):
        lower, idx = int8_lower_bounds(queries, codes, scales, err, qnorm,
                                       base)
        s_loc, i_loc = topk_smallest(
            lower, jnp.broadcast_to(idx[None, :], lower.shape), r + 1
        )
        s, i = sort_pairs(jnp.concatenate([lb, s_loc], axis=-1),
                          jnp.concatenate([li, i_loc], axis=-1))
        return s[:, : r + 1], i[:, : r + 1]

    return step


def quantized_norm_sq(q: jax.Array, scales: jax.Array) -> jax.Array:
    """EXACT ||x_hat||^2 = s_x^2 * sum(q_x^2) of the dequantized rows.

    One formula, used by every QuantizedDataset producer (quantize time and
    store-view rebuilds), so raw-path and engine-path bounds agree bitwise.
    """
    qf = q.astype(jnp.float32)
    return scales.astype(jnp.float32) ** 2 * jnp.sum(qf * qf, axis=-1)


def quantize_dataset(x: jax.Array) -> QuantizedDataset:
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / scales[:, None]), -127, 127).astype(jnp.int8)
    # exact per-row quantization error (tighter than the sqrt(d)/2 worst case)
    e = x32 - q.astype(jnp.float32) * scales[:, None]
    err = jnp.sqrt(jnp.sum(e * e, axis=-1))
    norms = jnp.sum(x32 * x32, axis=-1)
    return QuantizedDataset(q, scales, err, norms, quantized_norm_sq(q, scales))


_quantize_jit = jax.jit(quantize_dataset)


def quantize_rows(x, block_rows: int = 65536) -> QuantizedDataset:
    """:func:`quantize_dataset` of a host (N, d) array, returned as host
    (numpy) arrays and computed `block_rows` rows at a time.

    Quantization is row-independent, so blocking changes no value; it
    bounds the device memory one call takes to a block (a whole corpus
    shard, e.g. 4 GB of GIST or more, would not fit beside its resident
    f32 view on a 16 GB chip)."""
    x = np.asarray(x, dtype=np.float32)
    parts = [jax.device_get(_quantize_jit(x[i: i + block_rows]))
             for i in range(0, max(1, x.shape[0]), block_rows)]
    return QuantizedDataset(*(np.concatenate(col) for col in zip(*parts)))


def _approx_l2(qv: jax.Array, ds: QuantizedDataset) -> jax.Array:
    """d_hat = ||q - x_hat||^2 using the int8 dataset (f32 queries).

    <q, x_hat> = s_x * <q, q_x>; the GEMM runs with int8 dataset operand —
    on TPU the dataset side streams from HBM at 1 byte/element. The result
    is the EXACT quantized-approximation distance (qnorm_sq is the true
    ||x_hat||^2), which is what makes the reverse-triangle bounds in
    :func:`knn_quantized` sound.
    """
    q32 = qv.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    # (M, d) f32 x (N, d) i8 -> f32. XLA promotes the i8 operand lazily;
    # HBM traffic for the dataset stays 1B/elem.
    cross = jax.lax.dot_general(
        q32, ds.q.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT_PRECISION,
    )
    cross = cross * ds.scales[None, :]
    return jnp.maximum(qn - 2.0 * cross + ds.qnorm_sq[None, :], 0.0)


@functools.partial(jax.jit, static_argnames=("k", "rescore_factor"))
def knn_quantized(
    queries: jax.Array,
    ds: QuantizedDataset,
    full_vectors: jax.Array,
    k: int,
    rescore_factor: int = 4,
) -> tuple[TopK, jax.Array]:
    """Exact kNN with an int8 first pass and f32 rescore.

    Returns (topk, exact_certificate). certificate[i] is True iff the rescore
    budget provably covered every candidate that could belong to query i's
    true top-k (lower/upper bound argument above).
    """
    m = queries.shape[0]
    n = ds.q.shape[0]
    r = min(n, rescore_factor * k)

    valid = jnp.isfinite(ds.norms_sq)  # False on padding / tombstones
    d_hat = _approx_l2(queries, ds)  # (M, N) exact ||q - x_hat||^2
    q32 = queries.astype(jnp.float32)
    root = jnp.sqrt(d_hat)  # ||q - x_hat||
    e = ds.err[None, :]
    # reverse-triangle bracket around the true distance (module docstring)
    lower = jnp.where(valid[None, :],
                      jnp.maximum(root - e, 0.0) ** 2, jnp.inf)
    upper = jnp.where(valid[None, :], (root + e) ** 2, jnp.inf)

    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (m, n))
    # k-th smallest upper bound = certified pruning threshold
    ub_k, _ = topk_smallest(upper, idx, k)
    thresh = ub_k[:, -1:]
    # candidates: r smallest lower bounds
    cand_lb, cand_idx = topk_smallest(lower, idx, r)
    # certificate: every row OUTSIDE the candidate set has lower > thresh,
    # i.e. the (r+1)-th smallest lower bound exceeds the threshold (or r==n).
    # An infinite (r+1)-th lower bound means the candidate set already holds
    # every valid row — trivially certified even when thresh is also inf.
    if r < n:
        lb_r1, _ = topk_smallest(lower, idx, r + 1)
        certificate = (lb_r1[:, -1] > thresh[:, 0]) | ~jnp.isfinite(lb_r1[:, -1])
    else:
        certificate = jnp.ones((m,), dtype=bool)

    # exact f32 rescore of the candidates (invalid rows can only reach the
    # candidate set when fewer than r valid rows exist; mask them out here)
    cand_vecs = full_vectors[cand_idx]  # (M, r, d) gather
    diff = q32[:, None, :] - cand_vecs.astype(jnp.float32)
    exact_d = jnp.sum(diff * diff, axis=-1)
    cand_ok = (cand_idx >= 0) & valid[cand_idx]
    exact_d = jnp.where(cand_ok, exact_d, jnp.inf)
    s, i = topk_smallest(exact_d, cand_idx, k)
    i = jnp.where(jnp.isfinite(s), i, -1)  # drain empty queue slots as -1
    return TopK(s, i), certificate
