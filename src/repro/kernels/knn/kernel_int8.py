"""Fused int8 Pallas scan — 1 B/element dataset traffic, on-chip candidates.

The paper's FQ-SD throughput ceiling is memory bandwidth (section 5 names
quantization as the lever), so the int8 tier's whole point is bytes moved:
this kernel streams the int8 codes from HBM at 1 byte/element and never
materializes any (M, N) intermediate. Per grid step it

1. dot-accumulates one quantized (bm, bn) cross-product tile on the MXU
   into an f32 VMEM accumulator (the int8 tile is widened in VMEM, so HBM
   sees only the 1-byte codes);
2. applies the per-row scale dequant in the epilogue, forms the EXACT
   quantized-approximation distance d_hat = ||q - x_hat||^2 from the
   stored quantized norms, and lower-bounds the true squared-L2 distance
   with the ``repro.core.quantized`` reverse-triangle bound
   max(sqrt(d_hat) - err, 0)^2;
3. folds the tile's lower bounds into a VMEM-resident *widened* candidate
   queue of q_len = 2 * (rescore_budget) entries per query — wide so the
   caller can read both the rescore candidates (first half) and the
   (r+1)-th smallest lower bound that certifies them (entry r).

The certified exact rescore then happens outside the kernel and reads ONLY
the candidate rows of the f32 base tier (an (M, r) gather instead of a full
4 B/element pass) — see ``repro.kernels.knn.ops.knn_int8``.

The threshold-pruned queue merge is shared with the f32 kernel: strictly
worse tiles skip the bitonic sort + merge (strict ``>`` keeps pruning
bit-identical under ties — see ``kernel.py`` for the invariant), and
skipped-merge counts flush per m-tile for skip-rate reporting.
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


def _knn_int8_kernel(
    q_ref, x_ref, qn_ref, sc_ref, er_ref, hn_ref, ov_ref, oi_ref, sk_ref,
    acc, buf_v, buf_i, n_skip,
    *, q_len: int, n_steps: int, d_steps: int, bn: int, prune: bool,
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

    # int8 codes widen in VMEM; HBM traffic for the dataset stays 1 B/elem
    acc[...] += lax.dot_general(
        q_ref[...], x_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=EXACT_PRECISION,
    )

    @pl.when(kd == d_steps - 1)
    def _bound_and_enqueue():
        # per-row scale dequant epilogue: <q, x_hat> = s_x * <q, q_x>
        cross = acc[...] * sc_ref[...]  # (bm, bn) * (1, bn)
        hn = hn_ref[...]  # (1, bn) exact ||x_hat||^2; +inf marks invalid rows
        e = er_ref[...]  # (1, bn) certified ||e_x|| upper bound
        valid = jnp.isfinite(hn)
        # d_hat = ||q - x_hat||^2 EXACTLY (inf-safe on invalid rows), so the
        # reverse-triangle bound (sqrt(d_hat) - err)^2 <= d is sound; an
        # approximated quantized norm would drop the 2<x_hat, e> cross term
        # and overshoot the bound past true distances (see core.quantized)
        d_hat = jnp.maximum(
            qn_ref[...] - 2.0 * cross + jnp.where(valid, hn, 0.0), 0.0
        )
        lower = jnp.where(
            valid, jnp.maximum(jnp.sqrt(d_hat) - e, 0.0) ** 2, jnp.inf
        )
        idx = j * bn + lax.broadcasted_iota(jnp.int32, lower.shape, 1)

        def _merge():
            sv, si = bitonic_sort(lower, idx)
            buf_v[...], buf_i[...] = topk_update(
                buf_v[...], buf_i[...], sv[:, :q_len], si[:, :q_len]
            )

        if prune:
            skip = tile_prunable(lower, buf_v[...])
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
        "q_len", "block_m", "block_n", "block_d", "interpret", "prune",
    ),
)
def knn_pallas_int8(
    q: jax.Array,
    x8: jax.Array,
    qn: jax.Array,
    scales: jax.Array,
    err: jax.Array,
    hn: jax.Array,
    q_len: int,
    block_m: int = 128,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool = False,
    prune: bool = True,
):
    """Fused int8 candidate scan. Preconditions enforced by ops.py:
    M % bm == N % bn == d % bd == 0; q_len pow2 <= bn; q f32, x8 int8;
    scales/err/hn are (1, N) f32 with hn the EXACT quantized norm
    ||x_hat||^2 = s^2 * sum(q_x^2), set to +inf on invalid rows (padding /
    tombstones); err = 0 and scales = 1 on padding.

    Returns (lower bounds (M, q_len) sorted ascending, indices (M, q_len),
    skips (m_tiles, 1)). The first q_len//2 columns are the rescore
    candidates; column q_len//2 is the (r+1)-th smallest lower bound used
    by the exactness certificate.
    """
    m, d = q.shape
    n, _ = x8.shape
    bm, bn, bd = block_m, block_n, block_d
    if m % bm or n % bn or d % bd or q_len > bn:
        raise ValueError(
            f"bad blocking m{m} n{n} d{d} bm{bm} bn{bn} bd{bd} q_len{q_len}"
        )
    n_steps, d_steps = n // bn, d // bd
    grid = (m // bm, n_steps, d_steps)
    kern = functools.partial(
        _knn_int8_kernel, q_len=q_len, n_steps=n_steps, d_steps=d_steps,
        bn=bn, prune=prune,
    )
    v, i, sk = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bm, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, q_len), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((bm, q_len), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, j, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, q_len), jnp.float32),
            jax.ShapeDtypeStruct((m, q_len), jnp.int32),
            jax.ShapeDtypeStruct((m // bm * 8, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),  # int32->f32 cross accumulator
            pltpu.VMEM((bm, q_len), jnp.float32),  # candidate lower bounds
            pltpu.VMEM((bm, q_len), jnp.int32),  # candidate indices
            pltpu.SMEM((1,), jnp.int32),  # skipped merges of this m-tile
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary')),
        interpret=interpret,
    )(q, x8, qn, scales, err, hn)
    return v, i, sk[::8, :1]
