"""Streaming top-k Pallas kernel — the paper's kNN queue as a VMEM resident.

Consumes a pre-computed (M, N) score matrix tile-by-tile along N (grid minor
axis) and maintains, per query row, a sorted top-k buffer in VMEM scratch —
the direct analogue of the FPGA's k-element systolic queue, with the
element-serial compare-swap chain replaced by lane-parallel bitonic stages
(see repro.kernels.bitonic).

Per n-step work on a (bm, bn) tile:
    bitonic sort of the tile rows            log^2(bn) stages
    queue merge (reverse + min + merge)      log(k)+1  stages
versus the queue's bn cycles — the VPU trades cycles for lanes.

Scratch persists across the sequential n grid steps (TPU grid is a sequential
loop with double-buffered input pipelining); results flush on the last step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import bitonic_sort, topk_update


def _topk_kernel(
    s_ref, ov_ref, oi_ref, buf_v, buf_i, *, k_eff: int, n_steps: int, bn: int
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        buf_v[...] = jnp.full_like(buf_v, jnp.inf)
        buf_i[...] = jnp.full_like(buf_i, -1)

    tile = s_ref[...].astype(jnp.float32)  # (bm, bn)
    base = j * bn
    idx = base + lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    sv, si = bitonic_sort(tile, idx)
    buf_v[...], buf_i[...] = topk_update(
        buf_v[...], buf_i[...], sv[:, :k_eff], si[:, :k_eff]
    )

    @pl.when(j == n_steps - 1)
    def _flush():
        ov_ref[...] = buf_v[...]
        oi_ref[...] = buf_i[...]


@functools.partial(jax.jit, static_argnames=("k_eff", "block_m", "block_n", "interpret"))
def topk_pallas(
    scores: jax.Array,
    k_eff: int,
    block_m: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
):
    """(M, N) -> ((M, k_eff), (M, k_eff)). Preconditions (see ops.py):
    k_eff power of two, k_eff <= block_n, M % block_m == 0, N % block_n == 0.
    """
    m, n = scores.shape
    bm, bn = block_m, block_n
    if n % bn or m % bm:
        raise ValueError(f"({m},{n}) not divisible by ({bm},{bn})")
    if k_eff > bn:
        raise ValueError(f"k_eff={k_eff} must be <= block_n={bn}")
    n_steps = n // bn
    grid = (m // bm, n_steps)
    kern = functools.partial(_topk_kernel, k_eff=k_eff, n_steps=n_steps, bn=bn)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k_eff), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((m, k_eff), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, k_eff), jnp.float32),
            pltpu.VMEM((bm, k_eff), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
    )(scores)
