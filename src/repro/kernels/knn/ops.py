"""Jitted public wrappers for the fused kNN kernels (engine backend="pallas").

Three entry points:

* :func:`knn`              — fused f32/bf16 scan (l2 | ip | cos). cos is
                             served by pre-normalizing rows and reusing the
                             ip epilogue (1 - <q_hat, x_hat>), so every
                             metric runs through one kernel family.
* :func:`knn_int8`         — fused int8 scan (1 B/element dataset traffic)
                             with an on-chip widened candidate queue and a
                             certified exact f32 rescore that reads only
                             the candidate rows.
* :func:`knn_exact_direct` — chunked exact scan in the direct (q - x)^2
                             form; the bit-exact oracle/fallback for the
                             quantized path (per-pair values are identical
                             to a full-sort oracle using the same formula).

All wrappers handle padding; `block_*` arguments come from the per-device
autotuner (``repro.tuning``) via the planner, defaulting to
:data:`DEFAULT_BLOCKS`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.partition import next_pow2
from repro.core.quantized import QuantizedDataset
from repro.core.topk import TopK, sort_pairs
from repro.kernels.knn.kernel import knn_pallas
from repro.kernels.knn.kernel_int8 import knn_pallas_int8

#: Hand-tuned fallback (bm, bn, bd) used when the autotune cache is cold.
DEFAULT_BLOCKS = (128, 512, 512)

#: Scoped VMEM Mosaic lets one kernel use on a TPU v5e core when the
#: kernel sets no ``vmem_limit_bytes`` (the kernels here set none). This is
#: the compiler's default scope, not the core's physical VMEM (128 MiB on
#: v5e), and it holds for v5e only: other devices need their own figure.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

#: Live (value, index) copies of the (bm, bn) tile while it is sorted: the
#: tile, both rotations of each array and the selected result.
SORT_LIVE_PAIRS = 6


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def resolved_blocks(
    k: int,
    d: int,
    block_m: int = DEFAULT_BLOCKS[0],
    block_n: int = DEFAULT_BLOCKS[1],
    block_d: int = DEFAULT_BLOCKS[2],
    rescore_factor: int | None = None,
) -> tuple[int, int, int]:
    """The (bm, bn, bd) the kernels ACTUALLY run after legality clamps:
    bn grows to hold the on-chip queue, bd shrinks to the padded dim.

    Single source of truth — :func:`knn` / :func:`knn_int8` resolve their
    tiles through this, and the executors call it to report honest tile
    shapes in kernel_stats. ``rescore_factor=None`` means the f32 kernel
    (queue width k_eff); an int means the int8 kernel (queue width
    2 x next_pow2(rescore_factor * k_eff))."""
    queue = queue_width(k, rescore_factor)
    return (block_m, max(block_n, queue),
            min(block_d, _round_up(max(d, 1), 128)))


def queue_width(k: int, rescore_factor: int | None = None) -> int:
    """Per-query on-chip queue width: k_eff for the f32 kernel, 2r with
    r = next_pow2(rescore_factor * k_eff) for the int8 kernel."""
    k_eff = next_pow2(k)
    if rescore_factor is None:
        return k_eff
    return 2 * next_pow2(max(1, rescore_factor) * k_eff)


def vmem_bytes(
    k: int,
    d: int,
    block_m: int = DEFAULT_BLOCKS[0],
    block_n: int = DEFAULT_BLOCKS[1],
    block_d: int = DEFAULT_BLOCKS[2],
    rescore_factor: int | None = None,
) -> int:
    """Estimated VMEM one fused-kernel instance needs at its resolved tiles.

    Double-buffered query and dataset tiles, the int8 tile's f32 widening,
    the cross-product accumulator, the queue scratch, and the bitonic
    sort's working set, counted as SORT_LIVE_PAIRS live (value, index)
    copies of the (bm, bn) tile. Calibrated against Mosaic's compile for a
    TPU v5e at the default blocks: the int8 kernel with a 1024-wide queue
    fits VMEM_LIMIT_BYTES, with a 2048-wide one it runs out of VMEM."""
    bm, bn, bd = resolved_blocks(k, d, block_m, block_n, block_d,
                                 rescore_factor)
    queue = queue_width(k, rescore_factor)
    x_bytes = 4 if rescore_factor is None else 1
    widened = 0 if rescore_factor is None else bn * bd * 4
    return (2 * (bm * bd * 4 + bn * bd * x_bytes) + widened
            + bm * bn * 4 + bm * queue * 8 + SORT_LIVE_PAIRS * bm * bn * 8)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "metric", "block_m", "block_n", "block_d", "interpret",
        "prune", "return_stats", "x_prenormalized",
    ),
)
def knn(
    q: jax.Array,
    x: jax.Array,
    k: int,
    metric: str = "l2",
    x_norms: jax.Array | None = None,
    block_m: int = 128,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool | None = None,
    prune: bool = True,
    return_stats: bool = False,
    x_prenormalized: bool = False,
):
    """Exact kNN of (M, d) queries over (N, d) dataset -> TopK((M,k),(M,k)).

    Handles all padding: d zero-padded (exact for both metrics), N padded
    with +inf-norm rows (excluded by the in-kernel validity mask), k rounded
    to a power of two for the bitonic queue then sliced. If `x_norms` is
    given (engine-resident datasets precompute them) padded entries must
    already be +inf.

    metric="cos" pre-normalizes query and dataset rows (zero rows stay
    zero, matching `cosine_distance`'s "distance 1" convention) and reuses
    the ip epilogue: cos(q, x) distance = 1 + (-<q_hat, x_hat>). The +1
    shift is monotonic, so ordering and tie-breaking are untouched.
    Normalizing the dataset is an O(N*d) pass, so engines that serve cos
    from a resident view normalize it ONCE at fit time (cos is
    scale-invariant) and pass `x_prenormalized=True`; then only the (M, d)
    queries are normalized per call. `x_norms` stays the raw-norm validity
    channel (+inf = padding/tombstone) either way.

    `prune` enables the threshold-pruned queue merge (bit-identical results
    either way; see kernel.py). With `return_stats=True` the result is
    (TopK, skip_rate) where skip_rate is the fraction of tile merges the
    insertion filter skipped.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if metric not in ("l2", "ip", "cos"):
        raise ValueError(f"fused kernel supports l2|ip|cos, got {metric}")
    if x_norms is None:
        xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    else:
        xn = x_norms.astype(jnp.float32)

    kernel_metric = metric
    if metric == "cos":
        # pre-normalize rows, reuse the ip epilogue. Zero rows (norm 0) and
        # padded rows (norm +inf) both normalize to zero vectors -> ip 0 ->
        # distance 1; padded rows are additionally masked by xn = +inf.
        q32 = q.astype(jnp.float32)
        qn_row = jnp.sqrt(jnp.sum(q32 * q32, axis=-1, keepdims=True))
        q = jnp.where(qn_row > 0, q32 / jnp.maximum(qn_row, 1e-30), 0.0)
        if not x_prenormalized:
            x32 = x.astype(jnp.float32)
            xn_row = jnp.sqrt(xn)[:, None]
            x = jnp.where(
                jnp.isfinite(xn_row) & (xn_row > 0),
                x32 / jnp.maximum(xn_row, 1e-30), 0.0,
            )
        else:
            # resident pre-normalized views keep their storage dtype; the
            # fused kernel requires q/x to share one dtype, so follow x
            q = q.astype(x.dtype)
        kernel_metric = "ip"

    m, d = q.shape
    n, _ = x.shape
    k_eff = next_pow2(k)
    bm, bn, bd = resolved_blocks(k, d, block_m, block_n, block_d)
    mp, np_, dp = _round_up(m, bm), _round_up(n, bn), _round_up(d, bd)

    qp = jnp.pad(q, ((0, mp - m), (0, dp - d)))
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    xn = jnp.pad(xn, (0, np_ - n), constant_values=jnp.inf)[None, :]

    v, i, skips = knn_pallas(qp, xp, xn, k_eff, kernel_metric, bm, bn, bd,
                             interpret, prune)
    v, i = v[:m, :k], i[:m, :k]
    if metric == "cos":
        v = v + 1.0  # -<q_hat, x_hat> -> cosine distance (+inf stays +inf)
    out = TopK(v, jnp.where(jnp.isfinite(v), i, -1))
    if not return_stats:
        return out
    merges = (mp // bm) * (np_ // bn)
    skip_rate = jnp.sum(skips).astype(jnp.float32) / merges
    return out, skip_rate


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "rescore_factor", "block_m", "block_n", "block_d", "interpret",
        "prune", "return_stats",
    ),
)
def knn_int8(
    q: jax.Array,
    ds: QuantizedDataset,
    full_vectors: jax.Array,
    k: int,
    rescore_factor: int = 4,
    block_m: int = 128,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool | None = None,
    prune: bool = True,
    return_stats: bool = False,
):
    """Exact kNN with a fused int8 first pass and a candidate-only rescore.

    The int8 scan (``knn_pallas_int8``) keeps a widened on-chip queue of
    q_len = 2r certified lower bounds per query, r = next_pow2(
    rescore_factor * next_pow2(k)). The epilogue here:

    1. gathers ONLY the r candidate rows from `full_vectors` and rescores
       them exactly in f32 via the direct (q - x)^2 form — bit-identical to
       :func:`knn_exact_direct` / a full-sort oracle over the same rows;
    2. certifies: every row outside the candidate set has lower bound
       >= the queue's (r+1)-th entry; if that exceeds the k-th smallest
       *exact* candidate distance, no outside row can reach the top-k, so
       the returned top-k is provably the global exact answer.

    Returns (TopK, certificate (m,) bool), plus the pruning skip rate when
    `return_stats=True`. Requires q, ds.q and full_vectors to share one
    (padded) feature width, and ds.q / full_vectors one row count — the
    DatasetStore guarantees both for its tier views.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, d = q.shape
    n, d8 = ds.q.shape
    if d != d8 or full_vectors.shape != (n, d8):
        raise ValueError(
            f"geometry mismatch: q {q.shape}, int8 {ds.q.shape}, "
            f"f32 {full_vectors.shape} (tiers must share padded shapes)"
        )
    k_eff = next_pow2(k)
    r = next_pow2(max(1, rescore_factor) * k_eff)
    q_len = 2 * r
    bm, bn, bd = resolved_blocks(k, d, block_m, block_n, block_d,
                                 rescore_factor=rescore_factor)
    mp, np_, dp = _round_up(m, bm), _round_up(n, bn), _round_up(d, bd)

    q32 = q.astype(jnp.float32)
    qp = jnp.pad(q32, ((0, mp - m), (0, dp - d)))
    x8 = jnp.pad(ds.q, ((0, np_ - n), (0, dp - d)))
    qn = jnp.sum(qp * qp, axis=-1, keepdims=True)
    scales = jnp.pad(ds.scales.astype(jnp.float32), (0, np_ - n),
                     constant_values=1.0)[None, :]
    err = jnp.pad(ds.err.astype(jnp.float32), (0, np_ - n))[None, :]
    # validity rides norms_sq (+inf on tombstones; the only channel
    # mutations refresh), folded onto the exact quantized norms the
    # kernel's certified bound requires
    hn = jnp.where(jnp.isfinite(ds.norms_sq),
                   ds.qnorm_sq.astype(jnp.float32), jnp.inf)
    hn = jnp.pad(hn, (0, np_ - n), constant_values=jnp.inf)[None, :]

    lb, li, skips = knn_pallas_int8(qp, x8, qn, scales, err, hn, q_len,
                                    bm, bn, bd, interpret, prune)
    lb, li = lb[:m], li[:m]

    # certified exact rescore: read only the candidate rows of the f32 tier
    cand_idx = li[:, :r]
    cand_ok = cand_idx >= 0  # unfilled queue slots stay (inf, -1)
    cand_vecs = full_vectors[jnp.where(cand_ok, cand_idx, 0)]
    diff = q32[:, None, :] - cand_vecs.astype(jnp.float32)
    exact_d = jnp.sum(diff * diff, axis=-1)
    exact_d = jnp.where(cand_ok, exact_d, jnp.inf)
    s, i = sort_pairs(exact_d, cand_idx)  # lexicographic: exact tie order
    s, i = s[:, :k], i[:, :k]
    i = jnp.where(jnp.isfinite(s), i, -1)

    # certificate: min lower bound OUTSIDE the candidate set (= queue entry
    # r) must exceed the k-th smallest exact candidate distance; an
    # infinite entry means the candidates already hold every valid row.
    thresh = s[:, k - 1]
    lb_r1 = lb[:, r]
    certificate = (lb_r1 > thresh) | ~jnp.isfinite(lb_r1)

    out = TopK(s, i)
    if not return_stats:
        return out, certificate
    merges = (mp // bm) * (np_ // bn)
    skip_rate = jnp.sum(skips).astype(jnp.float32) / merges
    return out, certificate, skip_rate


@functools.partial(jax.jit, static_argnames=("k", "chunk_rows"))
def knn_exact_direct(
    q: jax.Array,
    x: jax.Array,
    norms: jax.Array,
    k: int,
    chunk_rows: int = 8192,
) -> TopK:
    """Chunked exact kNN in the DIRECT (q - x)^2 form (l2 only).

    Unlike `fqsd_scan` (which uses the qn - 2qx + xn cancellation form),
    every pairwise distance here is the literal f32 sum of squared
    differences — the same value, bit for bit, that `knn_int8`'s candidate
    rescore computes. Chunked merging is lexicographic ((value, index)
    sort), so the result is identical to a full-sort oracle over the same
    formula regardless of chunking: this is the exactness fallback for
    uncertified int8 queries AND the oracle the int8 tests compare against.

    `norms` carries the validity channel (+inf on padding/tombstones);
    N must be a multiple of chunk_rows (pad first).
    """
    m = q.shape[0]
    n, d = x.shape
    if n % chunk_rows:
        raise ValueError(f"N={n} not a multiple of chunk_rows={chunk_rows}")
    q32 = q.astype(jnp.float32)
    c = n // chunk_rows
    chunks = x.reshape(c, chunk_rows, d)
    norm_chunks = norms.reshape(c, chunk_rows)
    bases = jnp.arange(c, dtype=jnp.int32) * chunk_rows

    def body(state, xs):
        chunk, nb, base = xs
        diff = q32[:, None, :] - chunk[None, :, :].astype(jnp.float32)
        dmat = jnp.sum(diff * diff, axis=-1)
        dmat = jnp.where(jnp.isfinite(nb)[None, :], dmat, jnp.inf)
        idx = base + jnp.arange(chunk_rows, dtype=jnp.int32)
        idx = jnp.broadcast_to(idx[None, :], dmat.shape)
        s_all = jnp.concatenate([state[0], dmat], axis=-1)
        i_all = jnp.concatenate([state[1], idx], axis=-1)
        s, i = sort_pairs(s_all, i_all)
        return (s[:, :k], i[:, :k]), None

    init = (jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32))
    (s, i), _ = jax.lax.scan(body, init, (chunks, norm_chunks, bases))
    return TopK(s, jnp.where(jnp.isfinite(s), i, -1))
