"""Shared neural layers: RMSNorm, RoPE, GQA attention (full / blockwise-flash
/ decode), SwiGLU MLP, sort-based MoE dispatch.

Everything is functional: params are plain dict pytrees, init_* builds them,
apply functions are pure. Logical-axis sharding constraints come from
repro.runtime.sharding and are no-ops without a mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime.sharding import shard


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------------ RMSNorm
def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- RoPE
def rope_frequencies(d_head: int, theta: float = 1e4):
    half = d_head // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4):
    """x: (..., S, H, d_head); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)  # (half,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
def init_attention(key, d_model, n_heads, n_kv_heads, d_head, qkv_bias, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d_model, n_heads * d_head), dtype),
        "wk": _dense_init(ks[1], (d_model, n_kv_heads * d_head), dtype),
        "wv": _dense_init(ks[2], (d_model, n_kv_heads * d_head), dtype),
        "wo": _dense_init(ks[3], (n_heads * d_head, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * d_head,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * d_head,), dtype)
    return p


def _project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, rope_theta):
    b, s, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, n_heads, d_head)
    k = k.reshape(b, s, n_kv_heads, d_head)
    v = v.reshape(b, s, n_kv_heads, d_head)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa_full(q, k, v, causal: bool):
    """Reference attention; fine for short sequences / smoke tests."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits /= math.sqrt(dh)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_blockwise(q, k, v, causal: bool, q_block: int, kv_block: int):
    """Memory-efficient (flash-style) attention: lax.scan over KV blocks with
    running (max, sumexp, acc) — no (S, S) intermediate ever materializes.

    Shapes: q (B, Sq, H, dh); k/v (B, Skv, KVH, dh). GQA handled by folding
    the group into the head dim per q block.
    """
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    rep = h // kvh
    scale = 1.0 / math.sqrt(dh)
    nq = sq // q_block
    nk = skv // kv_block

    qb = q.reshape(b, nq, q_block, h, dh)
    kb = k.reshape(b, nk, kv_block, kvh, dh)
    vb = v.reshape(b, nk, kv_block, kvh, dh)

    def per_qblock(qi, q_tile):
        # q_tile: (b, q_block, h, dh)
        # NOTE: python loop, not lax.scan — (a) XLA cost_analysis counts a
        # while body once regardless of trip count, which would corrupt the
        # dry-run roofline; (b) per-step jax.checkpoint keeps the backward
        # working set at one tile (flash-bwd recompute).
        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, kj):
            m, l, acc = carry
            k_tile = kb[:, kj]  # (b, kv_block, kvh, dh)
            v_tile = vb[:, kj]
            k_rep = jnp.repeat(k_tile, rep, axis=2)
            v_rep = jnp.repeat(v_tile, rep, axis=2)
            s_ = jnp.einsum("bqhd,bkhd->bhqk", q_tile, k_rep).astype(jnp.float32)
            s_ *= scale
            if causal:
                qpos = qi * q_block + jnp.arange(q_block)
                kpos = kj * kv_block + jnp.arange(kv_block)
                mask = qpos[:, None] >= kpos[None, :]
                s_ = jnp.where(mask[None, None], s_, -jnp.inf)
            m_new = jnp.maximum(m, s_.max(axis=-1))
            # guard fully-masked rows (exp(-inf - -inf))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s_ - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s_), p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q.dtype), v_rep
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        a0 = jnp.zeros((b, h, q_block, dh), jnp.float32)
        carry = (m0, l0, a0)
        # causal early exit: kv blocks strictly above this q block's diagonal
        # contribute nothing — skip them at trace time (halves the flops, and
        # the dry-run roofline sees the real causal cost).
        last_kj = nk if not causal else min(
            nk, ((qi + 1) * q_block + kv_block - 1) // kv_block)
        for kj in range(last_kj):
            carry, _ = kv_step(carry, kj)
        m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (b, q_block, h, dh)

    outs = [per_qblock(qi, qb[:, qi]) for qi in range(nq)]
    return jnp.concatenate(outs, axis=1).reshape(b, sq, h, dh)


def attention(
    params,
    x,
    positions,
    *,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    rope_theta: float,
    causal: bool = True,
    flash_threshold: int = 2048,
    q_block: int = 512,
    kv_block: int = 1024,
    return_kv: bool = False,
):
    """Self-attention over (B, S, d_model); flash path beyond the threshold."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, rope_theta)
    if s > flash_threshold and s % q_block == 0 and s % kv_block == 0:
        o = _flash_blockwise(q, k, v, causal, q_block, kv_block)
    else:
        o = _sdpa_full(q, k, v, causal)
    o = o.reshape(b, s, n_heads * d_head)
    out = shard(o @ params["wo"], "batch", "seq", "embed")
    if return_kv:
        return out, k, v
    return out


def decode_attention(params, x, cache_k, cache_v, cache_len, *,
                     n_heads, n_kv_heads, d_head, rope_theta):
    """One-token decode against a KV cache (linear in cache length).

    x: (B, 1, d); cache_k/v: (B, S_max, KVH, dh); cache_len: scalar i32 —
    number of valid cache positions. Returns (out, new_k, new_v).
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_len, jnp.int32)
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, rope_theta)
    # one-hot masked insert, NOT dynamic_update_slice: a dynamic-offset
    # update on a sequence-sharded cache makes GSPMD all-gather the whole
    # cache every layer (2 GB/layer for qwen2.5 decode_32k — the dominant
    # baseline collective term, see EXPERIMENTS.md Perf iteration B). The
    # where() respects the sharding: each seq shard touches only itself.
    s_max = cache_k.shape[1]
    slot = (jnp.arange(s_max, dtype=jnp.int32) == cache_len)[None, :, None, None]
    new_k = jnp.where(slot, k.astype(cache_k.dtype), cache_k)
    new_v = jnp.where(slot, v.astype(cache_v.dtype), cache_v)
    mesh = jax.sharding.get_abstract_mesh()
    s_max = cache_k.shape[1]
    tp = mesh.axis_sizes[mesh.axis_names.index("model")] if (
        mesh is not None and not mesh.empty and "model" in mesh.axis_names) else 1
    if tp > 1 and s_max % tp == 0 and n_kv_heads % tp:
        # sequence-sharded cache: distributed flash-decode. A plain softmax
        # over the sharded seq axis makes GSPMD all-gather K AND V in f32
        # (2 GB/layer for qwen2.5 decode_32k); the manual island exchanges
        # only per-head (max, sum, o) statistics — O(B*H*dh) per chip.
        o = _flash_decode_sharded(
            q, new_k, new_v, cache_len, mesh=mesh,
            n_heads=n_heads, n_kv_heads=n_kv_heads, d_head=d_head)
    else:
        rep = n_heads // n_kv_heads
        k_all = jnp.repeat(new_k, rep, axis=2)
        v_all = jnp.repeat(new_v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_all).astype(jnp.float32)
        logits /= math.sqrt(d_head)
        valid = jnp.arange(s_max)[None, None, None, :] <= cache_len
        logits = jnp.where(valid, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)
    o = o.reshape(b, 1, n_heads * d_head)
    return o @ params["wo"], new_k, new_v


def _flash_decode_sharded(q, k, v, cache_len, *, mesh, n_heads, n_kv_heads, d_head):
    """Exact distributed softmax over a sequence-sharded KV cache.

    Each `model` shard scores its local cache slice, then (max, sumexp,
    weighted-V) statistics merge with pmax/psum — the flash-attention
    identity across chips. Wire bytes per layer: O(B*H*(dh+2)) instead of
    the cache itself.
    """
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    b = q.shape[0]
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    b_entry = data_axes if (data_axes and b % dp == 0) else None
    rep = n_heads // n_kv_heads
    scale = 1.0 / math.sqrt(d_head)

    def body(q_loc, k_loc, v_loc, clen):
        s_loc = k_loc.shape[1]
        my = lax.axis_index("model")
        k_all = jnp.repeat(k_loc, rep, axis=2)
        v_all = jnp.repeat(v_loc, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_loc, k_all).astype(jnp.float32)
        logits *= scale
        gpos = my * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        valid = (gpos <= clen)[None, None, None, :]
        logits = jnp.where(valid, logits, -jnp.inf)
        m_loc = logits.max(axis=-1)  # (b, h, 1)
        m = lax.pmax(m_loc, "model")
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(logits), jnp.exp(logits - m_safe[..., None]), 0.0)
        denom = lax.psum(p.sum(axis=-1), "model")  # (b, h, 1)
        o_part = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q_loc.dtype), v_all)
        o = lax.psum(o_part.astype(jnp.float32), "model")
        denom = jnp.maximum(denom, 1e-30)
        return (o / denom.transpose(0, 2, 1)[..., None]).astype(q_loc.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(b_entry, None, None, None),
                  P(b_entry, "model", None, None),
                  P(b_entry, "model", None, None), P()),
        out_specs=P(b_entry, None, None, None),
        check_vma=False,
    )(q, k, v, cache_len)


# ------------------------------------------------------------- SwiGLU MLP
def init_mlp(key, d_model, d_ff, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": _dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": _dense_init(ks[2], (d_ff, d_model), dtype),
    }


def mlp(params, x):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    h = shard(h, "batch", "seq", "ffn")
    return shard(h @ params["w_down"], "batch", "seq", "embed")


# ------------------------------------------------------------------- MoE
def init_moe(key, d_model, n_experts, d_expert, dtype):
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w_gate": _dense_init(ks[1], (n_experts, d_model, d_expert), dtype),
        "w_up": _dense_init(ks[2], (n_experts, d_model, d_expert), dtype),
        "w_down": _dense_init(ks[3], (n_experts, d_expert, d_model), dtype),
    }


def moe(params, x, *, n_experts: int, top_k: int, capacity_factor: float = 1.25):
    """Sort-based token-choice top-k MoE.

    Two execution paths with identical semantics:

    * meshless (smoke tests): global sort-based dispatch below.
    * mesh with a `model` axis: a fully-manual shard_map island — every chip
      dispatches ONLY its local tokens to ONLY its local experts (experts
      shard over `model`, tokens over data axes; activations are replicated
      over `model` by the TP layout) and the expert outputs combine with one
      psum over `model`. No global argsort, no cross-chip token gather: the
      1T-config dispatch buffer is (E/16, C_local, d) per chip instead of a
      GSPMD-replicated (T*topk, d) (which cost 1.7 TB/chip in the first
      dry-run — see EXPERIMENTS.md section Perf).

    Returns (y, aux) with aux = load-balance loss (Switch-style).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty and "model" in mesh.axis_names:
        return _moe_manual(params, x, n_experts=n_experts, top_k=top_k,
                           capacity_factor=capacity_factor, mesh=mesh)
    return _moe_dense(params, x, n_experts=n_experts, top_k=top_k,
                      capacity_factor=capacity_factor)


def _moe_dense(params, x, *, n_experts: int, top_k: int, capacity_factor: float):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ params["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, top_k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(t * top_k / n_experts * capacity_factor))
    capacity = max(capacity, 1)

    flat_e = gate_idx.reshape(-1)  # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    # position within expert: arange - start offset of this expert's run
    counts = jnp.bincount(sorted_e, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * top_k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos, t * 0 + n_experts * capacity)

    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[sorted_tok] * keep[:, None].astype(x.dtype))
    ebuf = buf[:-1].reshape(n_experts, capacity, d)
    ebuf = shard(ebuf, "expert", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", ebuf, params["w_up"])
    h = shard(h, "expert", None, None)
    eout = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    eout = shard(eout, "expert", None, None).reshape(n_experts * capacity, d)

    # return trip: gather each kept assignment's expert output
    contrib = jnp.where(keep[:, None], eout[jnp.minimum(slot, n_experts * capacity - 1)], 0)
    weights = gate_vals.reshape(-1)[order]
    y = jnp.zeros((t, d), jnp.float32).at[sorted_tok].add(
        contrib.astype(jnp.float32) * weights[:, None]
    )
    y = y.reshape(b, s, d).astype(x.dtype)

    # Switch load-balance aux: E * sum_e f_e * p_e
    dispatch_frac = jnp.bincount(flat_e, length=n_experts) / (t * top_k)
    router_frac = probs.mean(axis=0)
    aux = n_experts * jnp.sum(dispatch_frac * router_frac)
    return shard(y, "batch", "seq", "embed"), aux


def _moe_manual(params, x, *, n_experts: int, top_k: int,
                capacity_factor: float, mesh):
    """Expert-parallel MoE as a manual shard_map island (see moe() docstring).

    Layout contract: activations (B, S, d) shard B over the data axes and
    replicate over `model`; expert weights (E, d, de) shard E over `model`.
    Every chip routes its local tokens to its local E/tp experts and the
    per-chip expert outputs combine with one psum over `model` — collective
    volume identical to the TP MLP combine, dispatch entirely chip-local.
    """
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    tp = mesh.shape["model"]
    if n_experts % tp:
        raise ValueError(f"{n_experts} experts not divisible by model={tp}")
    e_loc = n_experts // tp
    b, s, d = x.shape
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    b_entry = data_axes if (data_axes and b % dp == 0) else None
    b_loc = b // dp if b_entry else b
    t_loc = b_loc * s
    capacity = max(4, int(math.ceil(t_loc * top_k / n_experts * capacity_factor)))

    def body(xl, router, wg, wu, wd):
        bl = xl.shape[0]
        t = bl * s
        xf = xl.reshape(t, d)
        logits = xf.astype(jnp.float32) @ router  # full E per chip
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        my = lax.axis_index("model")
        lo = my * e_loc
        flat_e = gate_idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
        flat_w = gate_vals.reshape(-1)
        is_local = (flat_e >= lo) & (flat_e < lo + e_loc)
        loc_e = jnp.where(is_local, flat_e - lo, e_loc)  # e_loc = trash bucket
        order = jnp.argsort(loc_e, stable=True)
        s_e, s_t, s_w = loc_e[order], flat_t[order], flat_w[order]
        counts = jnp.bincount(s_e, length=e_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t * top_k, dtype=jnp.int32) - starts[s_e]
        keep = (s_e < e_loc) & (pos < capacity)
        slot = jnp.where(keep, s_e * capacity + pos, e_loc * capacity)

        buf = jnp.zeros((e_loc * capacity + 1, d), xl.dtype)
        buf = buf.at[slot].set(xf[s_t] * keep[:, None].astype(xl.dtype))
        ebuf = buf[:-1].reshape(e_loc, capacity, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", ebuf, wu)
        eout = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * capacity, d)
        contrib = jnp.where(
            keep[:, None], eout[jnp.minimum(slot, e_loc * capacity - 1)], 0)
        y = jnp.zeros((t, d), jnp.float32).at[s_t].add(
            contrib.astype(jnp.float32) * s_w[:, None])
        # combine in bf16: halves the dominant MoE wire+HBM traffic (2x61
        # layers of (T_loc, d) per step); the f32 local accumulate above
        # keeps the per-chip sum exact before the cast (Perf iteration C).
        y = lax.psum(y.astype(jnp.bfloat16), "model")

        dispatch_frac = jnp.bincount(flat_e, length=n_experts) / (t * top_k)
        aux = n_experts * jnp.sum(dispatch_frac * probs.mean(axis=0))
        aux = lax.pmean(aux, ("model",) + tuple(data_axes))
        return y.reshape(bl, s, d).astype(x.dtype), aux

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(b_entry, None, None), P(), P("model"), P("model"), P("model")),
        out_specs=(P(b_entry, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
