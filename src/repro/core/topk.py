"""Exact top-k selection and merge — the TPU analogue of the paper's kNN queue.

The FPGA kNN queue is a systolic pipeline of k compare-swap nodes: every
incoming (distance, index) pair either displaces a stored minimum (op A) or
flows through (op B); on end-of-stream the k minima drain out sorted. The
semantics are exactly "streaming top-k smallest with stable drain order".

On TPU the element-serial queue becomes data-parallel selection:

* `topk_smallest`     — select k smallest of a score row block.
* `merge_topk`        — merge a running (M, k) state with fresh candidates;
                        the "insert a chunk into the queue" step used by the
                        FQ-SD streaming scan.
* `tree_merge_sorted` — exact associative merge of per-partition top-k
                        results (the distributed FD-SQ reduction).

All selections are exact; ties broken by smaller index (matching a stable
drain of the paper's queue where earlier-seen elements win ties).

"Exact" is f32 order with subnormals flushed to zero: XLA compares f32
with flush-to-zero on both the CPU and the TPU, so a subnormal score
(|s| < 2^-126) orders as a zero of its sign and ties with 0.0 — it is
returned unchanged, but ranked as zero. Distances that small are below
the rounding of any real distance computation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Sentinel for "no candidate": +inf score, -1 index.
INVALID_INDEX = jnp.int32(-1)


class TopK(NamedTuple):
    """Running kNN queue state: sorted ascending by score along the last axis."""

    scores: jax.Array  # (..., k) f32
    indices: jax.Array  # (..., k) i32

    @property
    def k(self) -> int:
        return self.scores.shape[-1]


def empty_topk(batch_shape: tuple[int, ...], k: int) -> TopK:
    """A queue full of +inf — the reset state of the paper's queue-nodes."""
    return TopK(
        scores=jnp.full((*batch_shape, k), jnp.inf, dtype=jnp.float32),
        indices=jnp.full((*batch_shape, k), INVALID_INDEX, dtype=jnp.int32),
    )


def sort_pairs(scores: jax.Array, indices: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort (scores, indices) ascending by (score, index) over the last axis.

    Two-key lexicographic lax.sort: exact ties resolve to the smaller index —
    the stable drain order of the systolic queue.
    """
    return jax.lax.sort((scores, indices), dimension=-1, num_keys=2)


def topk_smallest(
    scores: jax.Array, indices: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Exact k smallest entries of `scores` (last axis), ties to smaller index.

    scores : (..., n) f32, indices : (..., n) i32. If n < k the result is
    padded with +inf / -1 (a partially-filled queue).
    """
    scores = scores.astype(jnp.float32)
    indices = indices.astype(jnp.int32)
    n = scores.shape[-1]
    batch = scores.shape[:-1]
    if n <= k:
        s, i = sort_pairs(scores, indices)
        pad = k - n
        if pad:
            s = jnp.concatenate([s, jnp.full((*batch, pad), jnp.inf, s.dtype)], -1)
            i = jnp.concatenate(
                [i, jnp.full((*batch, pad), INVALID_INDEX, jnp.int32)], -1
            )
        return s, i
    # lax.top_k picks largest, so negate. On exact score ties top_k keeps the
    # earlier position; feeding candidates in ascending-index order therefore
    # keeps the smaller index, and the final two-key sort orders the selected
    # set. For adversarial inputs where equal scores straddle the k boundary
    # out of index order, selection among equals is index-arbitrary but the
    # returned *scores* are still exact; tests assert score-exactness and
    # index-validity (see tests/test_property.py).
    _, pos = jax.lax.top_k(-scores, k)
    gathered_s = jnp.take_along_axis(scores, pos, axis=-1)
    gathered_i = jnp.take_along_axis(indices, pos, axis=-1)
    return sort_pairs(gathered_s, gathered_i)


def merge_topk(state: TopK, scores: jax.Array, indices: jax.Array) -> TopK:
    """Insert a block of candidates into the running queue (exact).

    state.scores : (..., k); scores/indices : (..., c). Equivalent to feeding
    c more elements through the FPGA queue: result is the k smallest of the
    union, sorted.
    """
    all_s = jnp.concatenate([state.scores, scores.astype(jnp.float32)], axis=-1)
    all_i = jnp.concatenate([state.indices, indices.astype(jnp.int32)], axis=-1)
    s, i = topk_smallest(all_s, all_i, state.k)
    return TopK(s, i)


def merge_two_sorted(a: TopK, b: TopK) -> TopK:
    """Exact merge of two sorted top-k states (associative, commutative).

    The reduction operator for distributed FD-SQ: each dataset partition
    produces a local queue; merging all yields the global exact kNN (every
    global top-k element is necessarily in its partition's local top-k).
    """
    return merge_topk(a, b.scores, b.indices)


def tree_merge_sorted(parts_scores: jax.Array, parts_indices: jax.Array) -> TopK:
    """Merge P per-partition results, (P, ..., k) -> (..., k), via a binary tree.

    O(log P) merge stages instead of a serial O(P) chain — the multi-chip
    generalization of the paper's single shared FD-SQ queue. Its ops carry
    the scope name ``knn.tree_merge`` in the compiled program's metadata.
    """
    with jax.named_scope("knn.tree_merge"):
        s = parts_scores.astype(jnp.float32)
        i = parts_indices.astype(jnp.int32)
        k = s.shape[-1]
        while s.shape[0] > 1:
            p = s.shape[0]
            if p % 2:  # pad with one empty (drained) queue
                s = jnp.concatenate([s, jnp.full_like(s[:1], jnp.inf)], axis=0)
                i = jnp.concatenate([i, jnp.full_like(i[:1], INVALID_INDEX)],
                                    axis=0)
                p += 1
            half = p // 2
            cat_s = jnp.concatenate([s[:half], s[half:]], axis=-1)  # (half, ..., 2k)
            cat_i = jnp.concatenate([i[:half], i[half:]], axis=-1)
            s, i = topk_smallest(cat_s, cat_i, k)
        return TopK(s[0], i[0])


def knn_oracle(
    scores: jax.Array, k: int, base_index: int = 0
) -> tuple[jax.Array, jax.Array]:
    """Reference kNN from a dense (M, N) score matrix (smaller = closer)."""
    m, n = scores.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (m, n))
    s, i = topk_smallest(scores, idx, k)
    return s, jnp.where(i >= 0, i + base_index, i)
