"""Jitted public wrapper for the streaming top-k kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.partition import next_pow2
from repro.kernels.topk.kernel import topk_pallas


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("k", "block_m", "block_n", "interpret"))
def topk(
    scores: jax.Array,
    k: int,
    block_m: int = 128,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """k smallest per row of (M, N) scores: (values, indices) sorted asc.

    Pads N with +inf (never selected), M to the row block, k to the next
    power of two for the bitonic queue, then slices back. Out-of-range pad
    indices are mapped to -1.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, n = scores.shape
    k_eff = next_pow2(k)
    bn = max(block_n, k_eff)
    bm = block_m
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    s = jnp.pad(
        scores.astype(jnp.float32),
        ((0, mp - m), (0, np_ - n)),
        constant_values=jnp.inf,
    )
    v, i = topk_pallas(s, k_eff, bm, bn, interpret)
    v, i = v[:m, :k], i[:m, :k]
    return v, jnp.where(i < n, i, -1)
