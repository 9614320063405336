"""Fused exact-kNN Pallas kernel — the paper's whole dataflow in one pass.

On the FPGA, distances flow from the distance-computation pipelines straight
into the kNN queues; the (M, N) distance matrix never exists in memory. This
kernel reproduces that property on TPU: per grid step it computes one
(bm, bn) score tile on the MXU (accumulated over d blocks, like the
vector-adder) and immediately folds it into the VMEM-resident per-query
queues (bitonic top-k update). HBM traffic is exactly

    M*d + N*d (+ M*k out)   instead of   M*d + N*d + M*N

— for GIST (M=1e3, N=1e6) that removes a 4 GB intermediate; it converts the
operation from memory-bound to MXU-bound for any M >= ~6 (see roofline).

Grid (m_tiles, n_tiles, d_tiles): d innermost accumulates cross-products
into an f32 VMEM accumulator; on the last d step the tile is scored
(norm epilogue or negated IP), sorted, and merged into the queue scratch;
queues flush to HBM on the last (n, d) step. The sequential-grid input
pipelining (next (Q, X) tiles DMA while current tile computes) is the
paper's double-buffering at the VMEM tier.

Threshold-pruned queue merge (``prune=True``, the default): the queue
scratch ``buf_v`` is sorted ascending, so its last column is each query's
current kth-best score. Before sorting a tile, the kernel computes the
tile's row-wise minimum; when EVERY query's tile minimum is strictly worse
than its kth-best, no candidate in the tile can enter any queue and the
bitonic sort + merge are skipped (``repro.kernels.bitonic.tile_prunable``).

**Pruning invariant**: the skip test uses strict ``>``. A candidate that
ties the queue's worst value can still displace it via the lexicographic
(value, index) tie-break, so tying tiles are never pruned — the pruned
kernel is bit-identical (values AND indices) to the unpruned kernel on
every input, including tie-heavy ones (tested by tests/test_int8_pallas.py).
This is the paper's insertion filter: once queues warm up, the per-tile
sort runs rarely instead of always. Skipped-merge counts are emitted per
m-tile in the third output so callers can report the measured skip rate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distance import EXACT_PRECISION

from repro.kernels.bitonic import bitonic_sort, tile_prunable, topk_update


def _knn_kernel(
    q_ref, x_ref, qn_ref, xn_ref, ov_ref, oi_ref, sk_ref, acc, buf_v, buf_i,
    n_skip,
    *, k_eff: int, n_steps: int, d_steps: int, bn: int, metric: str,
    prune: bool,
):
    j = pl.program_id(1)
    kd = pl.program_id(2)

    @pl.when((j == 0) & (kd == 0))
    def _init_queue():
        buf_v[...] = jnp.full_like(buf_v, jnp.inf)
        buf_i[...] = jnp.full_like(buf_i, -1)
        n_skip[0] = 0

    @pl.when(kd == 0)
    def _init_acc():
        acc[...] = jnp.zeros_like(acc)

    # partial-distance / vector-adder: MXU cross-product accumulation
    acc[...] += lax.dot_general(
        q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=EXACT_PRECISION,
    )

    @pl.when(kd == d_steps - 1)
    def _score_and_enqueue():
        cross = acc[...]
        xn = xn_ref[...]  # (1, bn); +inf marks padded rows
        valid = jnp.isfinite(xn)
        if metric == "l2":
            s = jnp.maximum(qn_ref[...] - 2.0 * cross + xn, 0.0)
        else:  # ip
            s = -cross
        s = jnp.where(valid, s, jnp.inf)
        idx = j * bn + lax.broadcasted_iota(jnp.int32, s.shape, 1)

        def _merge():
            sv, si = bitonic_sort(s, idx)
            buf_v[...], buf_i[...] = topk_update(
                buf_v[...], buf_i[...], sv[:, :k_eff], si[:, :k_eff]
            )

        if prune:
            # kNN-queue insertion filter: sort+merge only when some row of
            # the tile can still beat that query's kth-best (strict >; ties
            # never prune — see module docstring).
            skip = tile_prunable(s, buf_v[...])
            pl.when(~skip)(_merge)

            @pl.when(skip)
            def _count_skip():
                n_skip[0] += 1
        else:
            _merge()

    @pl.when((j == n_steps - 1) & (kd == d_steps - 1))
    def _flush():
        ov_ref[...] = buf_v[...]
        oi_ref[...] = buf_i[...]
        # the SMEM counter leaves as one lane-aligned (8, 128) block per
        # m-tile (a (1, 1) block breaks the TPU tiling rule)
        sk_ref[...] = jnp.full(sk_ref.shape, n_skip[0], jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_eff", "metric", "block_m", "block_n", "block_d", "interpret",
        "prune",
    ),
)
def knn_pallas(
    q: jax.Array,
    x: jax.Array,
    xn: jax.Array,
    k_eff: int,
    metric: str = "l2",
    block_m: int = 128,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool = False,
    prune: bool = True,
):
    """Fused exact kNN. Preconditions enforced by ops.py:
    M % bm == N % bn == d % bd == 0; k_eff pow2 <= bn; xn is (1, N) with
    +inf on padded rows; q/x same dtype.

    Returns (values (M, k_eff), indices (M, k_eff), skips (m_tiles, 1)):
    `skips` counts threshold-pruned tile merges per m-tile (each m-tile has
    exactly n_tiles merge opportunities).
    """
    m, d = q.shape
    n, _ = x.shape
    bm, bn, bd = block_m, block_n, block_d
    if m % bm or n % bn or d % bd or k_eff > bn:
        raise ValueError(f"bad blocking m{m} n{n} d{d} bm{bm} bn{bn} bd{bd} k{k_eff}")
    qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    n_steps, d_steps = n // bn, d // bd
    grid = (m // bm, n_steps, d_steps)
    kern = functools.partial(
        _knn_kernel, k_eff=k_eff, n_steps=n_steps, d_steps=d_steps, bn=bn,
        metric=metric, prune=prune,
    )
    v, i, sk = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bm, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k_eff), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((bm, k_eff), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, j, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((m, k_eff), jnp.int32),
            jax.ShapeDtypeStruct((m // bm * 8, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),  # cross-product accumulator
            pltpu.VMEM((bm, k_eff), jnp.float32),  # queue values
            pltpu.VMEM((bm, k_eff), jnp.int32),  # queue indices
            pltpu.SMEM((1,), jnp.int32),  # skipped merges of this m-tile
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary')),
        interpret=interpret,
    )(q, x, qn, xn)
    return v, i, sk[::8, :1]
