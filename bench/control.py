"""The control: the reference, computed in the precision just below the one
the configuration states, put in the program's place.

The configuration states float32 products at ``Precision.HIGHEST``; the
step below, the one that would tempt a later change, is ``HIGH``: three
bf16 passes. On a TPU the control asks XLA for exactly that. Elsewhere
(a CPU's float32 dot ignores the precision flag) it writes the passes out
as a TPU v5e computes them: a = ah + al with ah the bf16 truncation of a
and al the bf16 rounding of the rest, a*b ~ ah*bh + ah*bl + al*bh. On
GIST-like data this reproduces the chip's HIGH error to the percent
(64 x 65,536 scores, max and rms error of 158.7 and 16.20 float32
roundings against the chip's 159.7 and 16.19; round-to-nearest halves
err a seventh as much). The halves are kept in float32 and multiplied at
HIGHEST, where products of bf16 values are exact; a cast to bfloat16 and
back would be elided by the compiler's excess-precision rewrite.
Scores use the same expansion as the service, ||q||^2 - 2<q, x> +
||x||^2 for l2 and -<q, x> for ip, and the top-k is exact over them: the
control differs from a sound run only in the precision of the products.
Its answers go through the same comparison as the program's, and must
fail it.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK_ROWS = 131072


def dot_high(a, b):
    """(m, d) x (n, d) -> (m, n) at three bf16 passes."""
    import jax
    import jax.numpy as jnp

    def dot(u, v, precision):
        return jax.lax.dot_general(u, v, (((1,), (1,)), ((), ())),
                                   precision=precision)

    if jax.default_backend() == "tpu":
        return dot(a, b, jax.lax.Precision.HIGH)

    def split(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                          jnp.float32)
        return hi, jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                            mantissa_bits=7)

    exact = jax.lax.Precision.HIGHEST
    ah, al = split(a)
    bh, bl = split(b)
    return dot(ah, bh, exact) + (dot(ah, bl, exact) + dot(al, bh, exact))


@functools.lru_cache(maxsize=None)
def _block_fn(k: int, metric: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(q, xb, base, best_s, best_i):
        cross = dot_high(q, xb)
        if metric == "l2":
            sc = (jnp.sum(q * q, 1, keepdims=True) - 2.0 * cross
                  + jnp.sum(xb * xb, 1)[None, :])
        else:
            sc = -cross
        ids = base + jnp.arange(xb.shape[0], dtype=jnp.int32)
        cs = jnp.concatenate([best_s, sc], 1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, sc.shape)], 1)
        neg, pos = jax.lax.top_k(-cs, k)
        return -neg, jnp.take_along_axis(ci, pos, 1)

    return block


def control_topk(x: np.ndarray, qs: np.ndarray, k: int,
                 metric: str) -> tuple[np.ndarray, np.ndarray]:
    """(ids, scores) of the k best rows of each query, ascending, computed
    on the default device in blocks of corpus rows."""
    import jax.numpy as jnp

    if metric not in ("l2", "ip"):
        raise ValueError(f"the control knows l2 and ip, not {metric!r}")
    block = _block_fn(k, metric)
    q = jnp.asarray(qs, jnp.float32)
    best_s = jnp.full((len(qs), k), jnp.inf, jnp.float32)
    best_i = jnp.full((len(qs), k), -1, jnp.int32)
    for s in range(0, x.shape[0], BLOCK_ROWS):
        xb = jnp.asarray(x[s: s + BLOCK_ROWS])
        best_s, best_i = block(q, xb, jnp.int32(s), best_s, best_i)
    return np.asarray(best_i).astype(np.int64), np.asarray(best_s)
