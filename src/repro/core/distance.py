"""Distance computation — the TPU adaptation of the paper's 3-stage pipeline.

The FPGA `distance-computation` block (partial-distance -> vector-adder ->
full-adder) produces one squared-L2 distance per query/vector pair by slicing
vectors into w-wide parts and accumulating partials. On TPU the same
reduction is expressed so the MXU does the heavy lifting:

    ||x - q||^2 = ||x||^2 - 2 <x, q> + ||q||^2

The <x, q> term over a (M x d) query block and a (N x d) dataset block is a
single GEMM on the 128x128 systolic array; the norm terms are cheap rank-1
epilogues. The r-slice accumulation of `partial-distance` corresponds to the
MXU's internal contraction over d. See DESIGN.md section 2.

All functions are pure jnp and jit-compatible; they are also the reference
oracles for the Pallas kernels in `repro.kernels`.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Metric = Literal["l2", "ip", "cos"]

_SUPPORTED: tuple[str, ...] = ("l2", "ip", "cos")

#: Precision of every f32 distance contraction. On a TPU the default
#: precision rounds f32 operands to bf16 (about 2^-8 relative error) before
#: the MXU; HIGHEST keeps the products f32-class, which the exact top-k and
#: the int8 certificate (repro.core.quantized) both assume. Pallas lowers
#: it to Mosaic's fp32 contract precision inside the fused kernels.
EXACT_PRECISION = jax.lax.Precision.HIGHEST


def validate_metric(metric: str) -> None:
    if metric not in _SUPPORTED:
        raise ValueError(f"metric must be one of {_SUPPORTED}, got {metric!r}")


def row_norms_sq(x: jax.Array) -> jax.Array:
    """||x_i||^2 per row, computed in f32 for stability."""
    x32 = x.astype(jnp.float32)
    return jnp.sum(x32 * x32, axis=-1)


def l2_sq(q: jax.Array, x: jax.Array, x_norms: jax.Array | None = None) -> jax.Array:
    """Squared euclidean distance matrix, (M, d) x (N, d) -> (M, N).

    Uses the norm expansion so the dominant cost is one GEMM (MXU-friendly).
    Accumulation in f32 regardless of input dtype (bf16 inputs supported).
    """
    q32 = q.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)  # (M, 1)
    xn = row_norms_sq(x) if x_norms is None else x_norms.astype(jnp.float32)
    # -2 <q, x> : contraction in f32 (preferred_element_type pins the MXU
    # accumulator width on TPU; HIGHEST keeps f32 inputs from being rounded
    # through one bf16 pass, which would misorder near-ties).
    cross = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT_PRECISION,
    )
    d = qn - 2.0 * cross + xn[None, :]
    # Guard tiny negatives from cancellation; distances are mathematically >= 0.
    return jnp.maximum(d, 0.0)


def inner_product(q: jax.Array, x: jax.Array) -> jax.Array:
    """<q, x> matrix, (M, d) x (N, d) -> (M, N), f32 accumulation."""
    return jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT_PRECISION,
    )


def cosine_distance(
    q: jax.Array, x: jax.Array, x_norms: jax.Array | None = None
) -> jax.Array:
    """1 - cos(q, x); zero vectors map to distance 1."""
    ip = inner_product(q, x)
    qn = jnp.sqrt(row_norms_sq(q))[:, None]
    xn = jnp.sqrt(row_norms_sq(x) if x_norms is None else x_norms.astype(jnp.float32))
    denom = jnp.maximum(qn * xn[None, :], 1e-30)
    return 1.0 - ip / denom


def pairwise_scores(
    q: jax.Array,
    x: jax.Array,
    metric: Metric = "l2",
    x_norms: jax.Array | None = None,
) -> jax.Array:
    """Uniform "smaller is better" score matrix for any supported metric.

    l2  -> squared distance
    ip  -> negated inner product (MIPS as a minimization, cf. paper
           section 4.1: maximum inner product / minimum euclidean norm)
    cos -> cosine distance
    """
    validate_metric(metric)
    if metric == "l2":
        return l2_sq(q, x, x_norms)
    if metric == "ip":
        return -inner_product(q, x)
    return cosine_distance(q, x, x_norms)


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_scores_jit(q, x, metric: Metric = "l2"):
    return pairwise_scores(q, x, metric)
