"""MXU-tiled squared-L2 distance kernel (Pallas TPU).

TPU adaptation of the paper's `distance-computation` pipeline (section 3.3):
the partial-distance / vector-adder / full-adder chain becomes a blocked
GEMM with a fused norm epilogue:

    D[i, j] = ||q_i||^2 - 2 <q_i, x_j> + ||x_j||^2

Grid: (M/bm, N/bn, d/bd). The d axis is the innermost ("arbitrary") grid
dimension; partial cross-products accumulate into the output tile across d
steps — exactly the vector-adder's B += A accumulation, with the MXU doing
w=128-wide partial distances per pass. Norm epilogue is applied on the last
d step (the full-adder).

VMEM per step: bm*bd + bn*bd + bm*bn floats. Defaults (bm=bn=256, bd=512)
-> 0.5 MB + 0.5 MB + 0.25 MB, comfortably double-bufferable in 16 MB VMEM
(Pallas pipelines the next (Q, X) tiles while the MXU consumes the current
ones — the kernel-level analogue of the paper's two memory banks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distance import EXACT_PRECISION


def _l2dist_kernel(q_ref, x_ref, qn_ref, xn_ref, o_ref, *, n_d_steps: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # partial-distance + vector-adder: accumulate -2 * Q X^T over d blocks
    q = q_ref[...]
    x = x_ref[...]
    part = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=EXACT_PRECISION,
    )
    o_ref[...] += -2.0 * part

    # full-adder epilogue: add norms once, on the final d step
    @pl.when(kd == n_d_steps - 1)
    def _epilogue():
        acc = o_ref[...]
        acc = acc + qn_ref[...] + xn_ref[...]
        o_ref[...] = jnp.maximum(acc, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_d", "interpret")
)
def l2dist_pallas(
    q: jax.Array,
    x: jax.Array,
    block_m: int = 256,
    block_n: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(M, d) x (N, d) -> (M, N) squared L2. Dims must divide by blocks."""
    m, d = q.shape
    n, dx = x.shape
    assert d == dx, (d, dx)
    bm, bn, bd = min(block_m, m), min(block_n, n), min(block_d, d)
    if m % bm or n % bn or d % bd:
        raise ValueError(f"shape ({m},{n},{d}) not divisible by blocks ({bm},{bn},{bd})")
    if q.dtype != x.dtype:
        raise ValueError(f"operand dtypes must match, got {q.dtype} vs {x.dtype}")
    qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)  # (M, 1)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True).T  # (1, N)
    n_d_steps = d // bd

    grid = (m // bm, n // bn, n_d_steps)
    return pl.pallas_call(
        functools.partial(_l2dist_kernel, n_d_steps=n_d_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bm, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, x, qn, xn)
