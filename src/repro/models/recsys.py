"""Recsys models: DLRM-RM2, Two-Tower retrieval, BST, Wide&Deep.

JAX has no native EmbeddingBag / CSR — lookups are built from jnp.take +
jax.ops.segment_sum (assignment brief). All categorical tables of a model are
FUSED into one (total_rows, dim) matrix with static per-feature row offsets;
one fused table = one row-sharded tensor over `model`, so the huge-table
lookup becomes: shard-local masked take -> psum over `model` (see
`embedding_lookup`), which is the collective-efficient pattern (traffic =
batch * dim, never table-sized).

The paper tie-in: two-tower `retrieval_cand` (1 query vs 10^6 candidates,
maximum inner product) is served by the exact-kNN engine (metric="ip") —
the paper's dense-retrieval use case verbatim.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import resolve, shard


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str  # "dlrm" | "two_tower" | "bst" | "wide_deep"
    table_sizes: tuple[int, ...]
    embed_dim: int
    n_dense: int = 0
    bot_mlp: tuple[int, ...] = ()
    top_mlp: tuple[int, ...] = ()
    tower_mlp: tuple[int, ...] = ()
    interaction: str = "dot"  # dot | concat | transformer-seq
    seq_len: int = 0
    n_heads: int = 0
    n_blocks: int = 0
    dtype: Any = jnp.float32

    @property
    def total_rows(self) -> int:
        return sum(self.table_sizes)

    @property
    def padded_rows(self) -> int:
        """Fused-table rows padded so every mesh axis combination divides
        evenly (shard_map rejects uneven shards). Pad rows are never
        addressed: ids stay below total_rows."""
        mult = 8192 if self.total_rows >= 1_000_000 else 32
        return ((self.total_rows + mult - 1) // mult) * mult

    @property
    def n_sparse(self) -> int:
        return len(self.table_sizes)

    def feature_offsets(self):
        off, acc = [], 0
        for s in self.table_sizes:
            off.append(acc)
            acc += s
        return jnp.asarray(off, jnp.int32)

    def params_count(self) -> int:
        def mlp_p(dims):
            return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        n = self.total_rows * self.embed_dim
        if self.kind == "dlrm":
            n += mlp_p((self.n_dense,) + self.bot_mlp)
            n_int = self.n_sparse + 1
            d_top_in = n_int * (n_int - 1) // 2 + self.bot_mlp[-1]
            n += mlp_p((d_top_in,) + self.top_mlp)
        elif self.kind == "two_tower":
            n += 2 * mlp_p((self.embed_dim,) + self.tower_mlp)
        elif self.kind == "bst":
            d = self.embed_dim
            n += self.n_blocks * (4 * d * d + 2 * d + 8 * d * d)  # attn + ffn
            n += mlp_p((d * 2,) + self.top_mlp) + self.top_mlp[-1] + 1
        elif self.kind == "wide_deep":
            n += self.total_rows  # wide weights (dim-1 tables)
            n += mlp_p((self.n_sparse * self.embed_dim,) + self.top_mlp)
        return n


# ------------------------------------------------------------ embeddings
def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """ids (...,) -> (..., dim). Row-sharded tables resolve via shard-local
    masked take + psum when a `model` mesh axis is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return jnp.take(table, ids, axis=0)

    from repro.runtime.sharding import sanitize_spec

    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    batch_spec = sanitize_spec(
        ids.shape, resolve(("batch",) + (None,) * (ids.ndim - 1)), sizes)
    out_spec = sanitize_spec(
        ids.shape + (table.shape[1],),
        resolve(("batch",) + (None,) * ids.ndim), sizes)

    def local(tbl, idv):
        size = tbl.shape[0]
        lo = lax.axis_index("model") * size
        loc = idv - lo
        ok = (loc >= 0) & (loc < size)
        vals = jnp.take(tbl, jnp.clip(loc, 0, size - 1), axis=0)
        vals = jnp.where(ok[..., None], vals, 0)
        return lax.psum(vals, "model")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model"), batch_spec), out_specs=out_spec,
        check_vma=False,
    )(table, ids)


def embedding_bag(table: jax.Array, ids: jax.Array, mode: str = "sum") -> jax.Array:
    """Multi-hot bag pooling: ids (B, L) with -1 padding -> (B, dim).

    take + masked segment-style sum (fixed-shape EmbeddingBag)."""
    mask = ids >= 0
    vals = embedding_lookup(table, jnp.maximum(ids, 0))
    vals = vals * mask[..., None].astype(vals.dtype)
    out = vals.sum(axis=-2)
    if mode == "mean":
        out = out / jnp.maximum(mask.sum(axis=-1, keepdims=True), 1).astype(out.dtype)
    return out


def _init_mlp(key, dims, dtype, final_bias=True):
    ks = jax.random.split(key, len(dims) - 1)
    return [
        {
            "w": (jax.random.normal(k, (a, b), jnp.float32) / math.sqrt(a)).astype(dtype),
            "b": jnp.zeros((b,), dtype),
        }
        for k, a, b in zip(ks, dims[:-1], dims[1:])
    ]


def _mlp(params, x, final_act=False):
    for i, lyr in enumerate(params):
        x = x @ lyr["w"] + lyr["b"]
        if final_act or i + 1 < len(params):
            x = jax.nn.relu(x)
    return x


def _init_table(key, rows, dim, dtype):
    return (jax.random.normal(key, (rows, dim), jnp.float32) * 0.01).astype(dtype)


# ------------------------------------------------------------------ init
def init(key: jax.Array, cfg: RecsysConfig):
    kt, k1, k2, k3 = jax.random.split(key, 4)
    params: dict = {"embed": _init_table(kt, cfg.padded_rows, cfg.embed_dim, cfg.dtype)}
    if cfg.kind == "dlrm":
        params["bot"] = _init_mlp(k1, (cfg.n_dense,) + cfg.bot_mlp, cfg.dtype)
        n_int = cfg.n_sparse + 1
        d_top_in = n_int * (n_int - 1) // 2 + cfg.bot_mlp[-1]
        params["top"] = _init_mlp(k2, (d_top_in,) + cfg.top_mlp, cfg.dtype)
    elif cfg.kind == "two_tower":
        params["user_tower"] = _init_mlp(k1, (cfg.embed_dim,) + cfg.tower_mlp, cfg.dtype)
        params["item_tower"] = _init_mlp(k2, (cfg.embed_dim,) + cfg.tower_mlp, cfg.dtype)
    elif cfg.kind == "bst":
        d = cfg.embed_dim
        params["pos"] = _init_table(k1, cfg.seq_len + 1, d, cfg.dtype)
        ks = jax.random.split(k2, 6)

        def _w(k, a, b):
            return (jax.random.normal(k, (a, b), jnp.float32) / math.sqrt(a)).astype(cfg.dtype)

        params["attn"] = {
            "wq": _w(ks[0], d, d), "wk": _w(ks[1], d, d),
            "wv": _w(ks[2], d, d), "wo": _w(ks[3], d, d),
            "ffn1": _init_mlp(ks[4], (d, 4 * d), cfg.dtype)[0],
            "ffn2": _init_mlp(ks[5], (4 * d, d), cfg.dtype)[0],
        }
        params["top"] = _init_mlp(k3, (2 * d,) + cfg.top_mlp + (1,), cfg.dtype)
    elif cfg.kind == "wide_deep":
        params["wide"] = _init_table(k1, cfg.padded_rows, 1, cfg.dtype)
        params["wide_bias"] = jnp.zeros((), cfg.dtype)
        params["deep"] = _init_mlp(
            k2, (cfg.n_sparse * cfg.embed_dim,) + cfg.top_mlp + (1,), cfg.dtype
        )
    else:
        raise ValueError(cfg.kind)
    return params


def param_specs(cfg: RecsysConfig):
    """Tables row-shard over `model`; MLPs replicate."""
    rows = resolve(("rows",))[0]
    sample = init(jax.random.key(0), dataclasses.replace(cfg, table_sizes=(8,) * cfg.n_sparse))
    specs = jax.tree.map(lambda _: P(), sample)
    specs["embed"] = P(rows, None)
    if cfg.kind == "wide_deep":
        specs["wide"] = P(rows, None)
    return specs


# --------------------------------------------------------------- forward
def _dlrm_forward(params, cfg, batch):
    dense = batch["dense"].astype(cfg.dtype)  # (B, n_dense)
    ids = batch["sparse"] + cfg.feature_offsets()[None, :]  # (B, n_sparse)
    emb = embedding_lookup(params["embed"], ids)  # (B, n_sparse, d)
    bot = _mlp(params["bot"], dense, final_act=True)  # (B, d)
    z = jnp.concatenate([bot[:, None, :], emb], axis=1)  # (B, F, d)
    z = shard(z, "batch", None, None)
    inter = jnp.einsum("bfd,bgd->bfg", z, z)  # (B, F, F) pairwise dots
    f = z.shape[1]
    iu, ju = jnp.triu_indices(f, k=1)
    flat = inter[:, iu, ju]  # (B, F(F-1)/2)
    top_in = jnp.concatenate([flat, bot], axis=-1)
    return _mlp(params["top"], top_in)[:, 0]  # logits (B,)


def _two_tower_embed(params, cfg, ids, tower):
    e = embedding_lookup(params["embed"], ids)
    v = _mlp(params[tower], e)
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


def _bst_forward(params, cfg, batch):
    d = cfg.embed_dim
    seq_ids = batch["seq"] + cfg.feature_offsets()[0]  # (B, S) item-id table
    tgt_ids = batch["target"] + cfg.feature_offsets()[0]  # (B,)
    e = embedding_lookup(params["embed"], seq_ids)  # (B, S, d)
    e = e + params["pos"][: cfg.seq_len][None]
    a = params["attn"]
    b, s, _ = e.shape
    h = cfg.n_heads
    dh = d // h
    q = (e @ a["wq"]).reshape(b, s, h, dh)
    k = (e @ a["wk"]).reshape(b, s, h, dh)
    v = (e @ a["wv"]).reshape(b, s, h, dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    mask = batch.get("seq_mask")
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(e.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, d) @ a["wo"]
    x = e + o
    x = x + (jax.nn.relu(x @ a["ffn1"]["w"] + a["ffn1"]["b"]) @ a["ffn2"]["w"] + a["ffn2"]["b"])
    pooled = x.mean(axis=1)
    tgt = embedding_lookup(params["embed"], tgt_ids)
    return _mlp(params["top"], jnp.concatenate([pooled, tgt], axis=-1))[:, 0]


def _wide_deep_forward(params, cfg, batch):
    ids = batch["sparse"] + cfg.feature_offsets()[None, :]  # (B, n_sparse)
    wide = embedding_lookup(params["wide"], ids)[..., 0].sum(-1) + params["wide_bias"]
    emb = embedding_lookup(params["embed"], ids)  # (B, F, d)
    deep = _mlp(params["deep"], emb.reshape(emb.shape[0], -1))[:, 0]
    return wide + deep


def forward(params, cfg: RecsysConfig, batch) -> jax.Array:
    if cfg.kind == "dlrm":
        return _dlrm_forward(params, cfg, batch)
    if cfg.kind == "bst":
        return _bst_forward(params, cfg, batch)
    if cfg.kind == "wide_deep":
        return _wide_deep_forward(params, cfg, batch)
    raise ValueError(f"forward() undefined for {cfg.kind}; use two_tower_* fns")


def loss_fn(params, cfg: RecsysConfig, batch) -> tuple[jax.Array, dict]:
    if cfg.kind == "two_tower":
        u = _two_tower_embed(params, cfg, batch["user"], "user_tower")
        v = _two_tower_embed(params, cfg, batch["item"], "item_tower")
        logits = (u @ v.T) / 0.05  # in-batch sampled softmax
        labels = jnp.arange(u.shape[0])
        logz = jax.nn.logsumexp(logits, axis=-1)
        nll = (logz - logits[labels, labels]).mean()
        return nll, {"nll": nll}
    logits = forward(params, cfg, batch).astype(jnp.float32)
    y = batch["label"].astype(jnp.float32)
    bce = jnp.mean(jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return bce, {"bce": bce}


# ----------------------------------------------------------------- serve
def serve_scores(params, cfg: RecsysConfig, batch) -> jax.Array:
    """Pointwise scoring (serve_p99 / serve_bulk shapes)."""
    if cfg.kind == "two_tower":
        u = _two_tower_embed(params, cfg, batch["user"], "user_tower")
        v = _two_tower_embed(params, cfg, batch["item"], "item_tower")
        return jnp.einsum("bd,bd->b", u, v)
    return jax.nn.sigmoid(forward(params, cfg, batch))


def retrieve_topk(params, cfg: RecsysConfig, user_ids, candidates, k: int):
    """retrieval_cand shape: exact MIPS over the candidate corpus via the
    paper's engine (FD-SQ dataflow, metric='ip')."""
    from repro.core.fqsd import chunk_step
    from repro.core.topk import empty_topk

    u = _two_tower_embed(params, cfg, user_ids, "user_tower")  # (B, d)
    state = empty_topk((u.shape[0],), k)
    n = candidates.shape[0]
    return chunk_step(state, u, candidates, None, 0, n, "ip")
