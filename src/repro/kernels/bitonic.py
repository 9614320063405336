"""Vectorized bitonic networks — lane-parallel compare-exchange primitives.

The FPGA kNN queue is a chain of k compare-swap nodes processing one element
per cycle. The VPU is an (8 x 128)-lane SIMD machine, so the element-serial
queue becomes O(log^2) *stages* of full-width compare-exchanges. Everything
here is written with roll/iota/where only (no gathers, no lane reshapes, no
reversal) so it lowers inside Pallas TPU kernels; the same functions double
as jnp reference code. Lane rotations go through ``pltpu.roll`` (Mosaic's
native lane rotate; outside a kernel it lowers to ``jnp.roll``), which
takes non-negative shifts only: a rotation by -s is one by L - s.

All arrays are (..., L) with L a power of two; (values, indices) move as
pairs and comparisons are lexicographic (value, index) so exact-score ties
break to the smaller index — identical semantics to the systolic queue's
stable drain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu


def _is_pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


def _pair_less(v1, i1, v2, i2):
    """(v1, i1) < (v2, i2) lexicographically."""
    return (v1 < v2) | ((v1 == v2) & (i1 < i2))


def _roll(x, shift, axis: int):
    """Lane rotation by a non-negative shift: ``pltpu.roll`` under tracing
    (in a kernel or a jit), ``jnp.roll`` on concrete arrays, because the
    Mosaic primitive has no eager evaluation rule."""
    if isinstance(x, jax.core.Tracer):
        return pltpu.roll(x, shift, axis)
    return jnp.roll(x, shift, axis)


def _partners(vals, idxs, s):
    """(vals, idxs) of each lane's XOR-distance-s partner, lane i ^ s,
    realized with two lane rotations + a bit mask (gather-free). `s` is a
    power of two, static or traced."""
    n = vals.shape[-1]
    axis = vals.ndim - 1
    lane = lax.broadcasted_iota(jnp.int32, vals.shape, axis)
    upper = (lane & s) != 0  # I am the +s element of my pair
    fwd_v = _roll(vals, n - s, axis)  # vals[i+s]
    bwd_v = _roll(vals, s, axis)  # vals[i-s]
    fwd_i = _roll(idxs, n - s, axis)
    bwd_i = _roll(idxs, s, axis)
    return jnp.where(upper, bwd_v, fwd_v), jnp.where(upper, bwd_i, fwd_i)


def _halving_stages(vals, idxs, s_top: int, stage):
    """Run ``stage(vals, idxs, s)`` for s = s_top, s_top/2, ..., 1.

    Lane-aligned widths (multiples of 128) run a rolled ``fori_loop`` with
    a traced distance s: one loop body per call instead of log(s_top)
    unrolled copies. Sorting a (128, 1024) tile then traces to 410 array
    ops instead of 1,925 (each op is 128 vregs of Mosaic work), and the
    queue merge to 60 instead of 399. Narrower queues unroll with static
    s: Mosaic refuses a traced rotation of a sub-128-lane vector
    (``tpu.dynamic_rotate``: "unsupported unaligned shape")."""
    if s_top < 1:
        return vals, idxs
    if vals.shape[-1] % 128:
        s = s_top
        while s >= 1:
            vals, idxs = stage(vals, idxs, s)
            s //= 2
        return vals, idxs
    n_stages = s_top.bit_length()  # s_top is a power of two

    def body(j, carry):
        s = jnp.right_shift(jnp.int32(s_top), j)
        return stage(carry[0], carry[1], s)

    return lax.fori_loop(0, n_stages, body, (vals, idxs))


def _reverse(vals, idxs):
    """Reverse the last axis (power-of-two length L) without ``rev``, which
    Pallas TPU cannot lower: lane i goes to lane i ^ (L - 1), i.e. one
    unconditional partner swap at every distance L/2, L/4, ..., 1."""
    return _halving_stages(vals, idxs, vals.shape[-1] // 2, _partners)


def _compare_exchange(vals, idxs, s, take_smaller):
    """One compare-exchange stage at XOR-distance s (s a power of two).

    take_smaller : bool array broadcastable to vals — True where this lane
    keeps the smaller of (self, partner); the partner of lane i is lane i^s.
    """
    part_v, part_i = _partners(vals, idxs, s)
    partner_smaller = _pair_less(part_v, part_i, vals, idxs)
    # and/or/not only: Mosaic cannot select between bool vectors
    choose_partner = ((take_smaller & partner_smaller)
                      | (~take_smaller & ~partner_smaller))
    out_v = jnp.where(choose_partner, part_v, vals)
    out_i = jnp.where(choose_partner, part_i, idxs)
    return out_v, out_i


def bitonic_sort(vals, idxs):
    """Full ascending bitonic sort over the last axis (length power of two).

    log^2(L) compare-exchange stages, each O(L) vectorized work across all
    leading axes — the throughput-form of the paper's one-element-per-cycle
    queue insertion.
    """
    L = vals.shape[-1]
    if not _is_pow2(L):
        raise ValueError(f"bitonic_sort needs power-of-two length, got {L}")
    k = 2
    while k <= L:
        def stage(v, i, s, k=k):
            lane = lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
            asc = (lane & k) == 0  # block direction alternates at span k
            lower = (lane & s) == 0
            take_smaller = (lower & asc) | (~lower & ~asc)
            return _compare_exchange(v, i, s, take_smaller)

        vals, idxs = _halving_stages(vals, idxs, k // 2, stage)
        k *= 2
    return vals, idxs


def bitonic_merge_ascending(vals, idxs):
    """Sort a *bitonic* (..., L) sequence ascending: log(L) stages."""
    L = vals.shape[-1]
    if not _is_pow2(L):
        raise ValueError(f"bitonic_merge needs power-of-two length, got {L}")

    def stage(v, i, s):
        lane = lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
        return _compare_exchange(v, i, s, (lane & s) == 0)

    return _halving_stages(vals, idxs, L // 2, stage)


def topk_update(buf_v, buf_i, cand_v, cand_i):
    """Streaming top-k update: merge k sorted-ascending candidates into a
    sorted-ascending (..., k) buffer. THE kernel-resident kNN queue step.

    buf asc + candidates asc:
      1. reverse candidates (desc; XOR-swap stages, see `_reverse`);
      2. lane-wise lexicographic min into the buffer — after this the buffer
         holds exactly the k smallest of the union (each buffer lane's
         partner in the would-be 2k bitonic sequence), and is itself bitonic;
      3. one bitonic merge re-sorts ascending.
    Cost: 2 log(k)+1 stages versus the FPGA queue's k-cycle drain.
    """
    if buf_v.shape != cand_v.shape:
        raise ValueError(f"buffer/candidates shape mismatch {buf_v.shape} vs {cand_v.shape}")
    rev_v, rev_i = _reverse(cand_v, cand_i)
    take_rev = _pair_less(rev_v, rev_i, buf_v, buf_i)
    v = jnp.where(take_rev, rev_v, buf_v)
    i = jnp.where(take_rev, rev_i, buf_i)
    return bitonic_merge_ascending(v, i)


def sort_topk_tile(scores, idxs, k_eff: int):
    """Sort a (..., L) tile ascending and return its first k_eff columns."""
    v, i = bitonic_sort(scores, idxs)
    return v[..., :k_eff], i[..., :k_eff]


def tile_prunable(scores, queue_vals):
    """True iff NO element of a (bm, bn) score tile can enter the queues.

    This is the paper's kNN-queue insertion filter lifted to tile
    granularity: once the per-query queues are warm, a whole tile whose
    row-wise minimum cannot beat the queue's current worst entry carries
    zero insertable candidates, so the O(log^2 bn) bitonic sort and the
    merge can be skipped entirely.

    Pruning invariant (what keeps the pruned kernel bit-identical to the
    unpruned one): the comparison is STRICTLY greater-than. A candidate
    whose score merely *ties* the queue's worst value can still displace it
    through the lexicographic (value, index) tie-break, so tiles touching
    the threshold are never pruned. `queue_vals` is sorted ascending, hence
    its last column is the per-query worst ("kth-best") value.
    """
    worst = queue_vals[..., -1:]  # (bm, 1): per-query kth-best
    tile_min = jnp.min(scores, axis=-1, keepdims=True)  # (bm, 1)
    return jnp.all(tile_min > worst)
