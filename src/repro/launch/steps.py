"""Cell builder: (architecture x shape) -> jit-able step + input specs +
shardings. Shared by the multi-pod dry-run, the benchmarks, and the smoke
tests (smoke=True swaps in the reduced model and tiny dims but exercises the
same step code).

A Cell bundles everything dryrun.py needs:
    fn             step callable (params-first)
    inputs         dict name -> ShapeDtypeStruct (global shapes)
    in_specs       pytree of PartitionSpec matching fn's positional args
    out_specs      pytree of PartitionSpec for outputs
    meta           dims used by the roofline (params, tokens, bytes, ...)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import gnn as G
from repro.models import recsys as R
from repro.models import transformer as T
from repro.models.sampler import edge_budget
from repro.optim import adamw_init, adamw_update, apply_updates
from repro.optim.adamw import AdamWConfig
from repro.runtime.sharding import resolve, sanitize_tree


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    fn: Callable
    inputs: tuple  # positional args as ShapeDtypeStructs (pytrees)
    in_specs: tuple
    out_specs: Any
    meta: dict


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _replicated(tree):
    return jax.tree.map(lambda _: P(), tree)


def _opt_specs(param_specs_tree, opt_shapes):
    """AdamWState spec tree: moments mirror param specs (same ZeRO-3/TP
    sharding); int8 _Q8 scale vectors replicate size-1 axes. param specs
    lead the map so one spec leaf covers a whole _Q8(q, scale) subtree."""

    def fix(shape_leaf, spec):
        sp = list(spec) + [None] * (len(shape_leaf.shape) - len(spec))
        for i, dim in enumerate(shape_leaf.shape):
            if dim == 1:
                sp[i] = None
        return P(*sp[: len(shape_leaf.shape)])

    def expand(spec_leaf, opt_subtree):
        return jax.tree.map(lambda leaf: fix(leaf, spec_leaf), opt_subtree)

    m = jax.tree.map(expand, param_specs_tree, opt_shapes.m)
    v = jax.tree.map(expand, param_specs_tree, opt_shapes.v)
    return type(opt_shapes)(step=P(), m=m, v=v)


# ============================================================== LM cells
def _lm_batch_specs():
    b = resolve(("batch",))[0]
    return {"tokens": P(b, None), "labels": P(b, None)}


def build_lm_cell(arch: ArchConfig, shape: ShapeSpec, smoke: bool = False) -> Cell:
    cfg: T.LMConfig = arch.smoke_model if smoke else arch.model
    if smoke:
        dims = {"train": (2, 16), "prefill": (2, 32), "decode": (2, 64)}[
            "train" if shape.kind == "train" else shape.kind
        ]
        batch, seq = dims
    else:
        batch, seq = shape["global_batch"], shape["seq_len"]

    params_shape = jax.eval_shape(lambda k: T.init(k, cfg), jax.random.key(0))
    training = shape.kind == "train"
    pspecs = (sanitize_tree(params_shape, T.param_specs(cfg, training=training), _mesh())
              if _mesh() else _replicated(params_shape))

    if shape.kind == "train":
        opt_cfg = AdamWConfig(lr=1e-4, moment_dtype=arch.train_moment_dtype)
        opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_shape)
        ospecs = _opt_specs(pspecs, opt_shape) if _mesh() else _replicated(opt_shape)
        mb = arch.train_microbatches if not smoke else 1

        def train_step(params, opt_state, batch_):
            if mb == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    T.loss_fn, has_aux=True)(params, cfg, batch_)
            else:
                # gradient accumulation: peak activation memory / mb at the
                # same tokens/step (Perf iteration C). Grads accumulate in
                # param dtype (bf16), sharded like params.
                tk = batch_["tokens"].reshape(mb, batch // mb, seq)
                lb = batch_["labels"].reshape(mb, batch // mb, seq)

                def mb_body(acc, xs):
                    g_acc, l_acc = acc
                    (l, _), g = jax.value_and_grad(
                        T.loss_fn, has_aux=True)(
                            params, cfg, {"tokens": xs[0], "labels": xs[1]})
                    g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                    return (g_acc, l_acc + l), None

                zeros = jax.tree.map(jnp.zeros_like, params)
                (grads, loss_sum), _ = lax.scan(
                    mb_body, (zeros, jnp.float32(0)), (tk, lb),
                    unroll=mb if cfg.scan_unroll else 1)
                grads = jax.tree.map(lambda g: g / mb, grads)
                loss = loss_sum / mb
                metrics = {"nll": loss, "moe_aux": jnp.float32(0)}
            updates, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
            params = apply_updates(params, updates)
            return params, opt_state, {"loss": loss, **metrics}

        batch_in = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        }
        bspecs = _lm_batch_specs() if _mesh() else {"tokens": P(), "labels": P()}
        metrics_specs = {"loss": P(), "nll": P(), "moe_aux": P()}
        return Cell(
            arch.arch_id, shape.name, shape.kind, train_step,
            (params_shape, opt_shape, batch_in),
            (pspecs, ospecs, bspecs),
            (pspecs, ospecs, metrics_specs),
            _lm_meta(cfg, batch, seq, train=True),
        )

    if shape.kind == "prefill":
        # 32k prefill: widen flash tiles (16x16 causal tile grid instead of
        # 64x32) — same math, 4x fewer inline tile groups to compile.
        cfg = dataclasses.replace(cfg, q_block=2048, kv_block=2048)
        params_shape = jax.eval_shape(lambda k: T.init(k, cfg), jax.random.key(0))

        def prefill_step(params, tokens):
            return T.prefill(params, cfg, tokens)

        tokens_in = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        b = resolve(("batch",))[0]
        cache_sp = _cache_specs(cfg, batch, seq)
        out_specs = (P(b, None), cache_sp)
        return Cell(
            arch.arch_id, shape.name, shape.kind, prefill_step,
            (params_shape, tokens_in),
            (pspecs, P(b, None) if _mesh() else P()),
            out_specs if _mesh() else None,
            _lm_meta(cfg, batch, seq, train=False),
        )

    # decode (decode_32k / long_500k): one new token against a seq_len cache
    cache_shape = jax.eval_shape(
        lambda: T.init_cache(cfg, batch, seq))
    cache_sp = _cache_specs(cfg, batch, seq) if _mesh() else _replicated(cache_shape)

    def decode(params, cache, tokens):
        return T.decode_step(params, cfg, cache, tokens)

    tokens_in = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    b = resolve(("batch",))[0]
    tok_sp = _san((batch, 1), P(b, None))
    logit_sp = _san((batch, cfg.vocab_padded), P(b, None))
    return Cell(
        arch.arch_id, shape.name, shape.kind, decode,
        (params_shape, cache_shape, tokens_in),
        (pspecs, cache_sp, tok_sp),
        (logit_sp, cache_sp),
        _lm_meta(cfg, batch, seq, train=False, decode=True),
    )


def _san(shape_tuple, spec):
    """Divisibility-sanitize a spec against the active mesh (no-op meshless)."""
    m = _mesh()
    if not m:
        return P()
    from repro.runtime.sharding import sanitize_spec

    return sanitize_spec(shape_tuple, spec, dict(zip(m.axis_names, m.axis_sizes)))


def _cache_specs(cfg: T.LMConfig, batch: int, seq: int):
    """(L, B, S, KV, dh): batch over data axes when divisible, else the
    sequence shards over `model` (long-context single-request case)."""
    if not _mesh():
        return {"k": P(), "v": P(), "len": P()}
    b_ax = resolve(("batch",))[0]
    tp = resolve(("heads",))[0]
    mesh = _mesh()
    b_div = batch % _axsize(mesh, b_ax) == 0 if b_ax else False
    kv_div = cfg.n_kv_heads % _axsize(mesh, tp) == 0 if tp else False
    b_entry = b_ax if b_div else None
    if kv_div:
        sp = P(None, b_entry, None, tp, None)
    else:
        sp = P(None, b_entry, tp, None, None)  # shard the cache sequence
    return {"k": sp, "v": sp, "len": P()}


def _axsize(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        return int(np.prod([mesh.shape[e] for e in entry]))
    return mesh.shape[entry]


def _lm_meta(cfg: T.LMConfig, batch, seq, train: bool, decode: bool = False):
    n_total = cfg.params_count()
    n_active = cfg.active_params_count()
    tokens = batch * (1 if decode else seq)
    model_flops = (6 if train else 2) * n_active * tokens
    if decode:
        # attention reads the whole cache: 2 * B * S * L * kv * dh * 2 matmuls
        model_flops += 4 * batch * seq * cfg.n_layers * cfg.n_kv_heads * cfg.d_head
    return {
        "family": "lm", "params_total": n_total, "params_active": n_active,
        "tokens": tokens, "model_flops": model_flops,
        "batch": batch, "seq": seq, "train": train,
    }


def _mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if (m is None or m.empty) else m


# ============================================================= GNN cells
def build_gnn_cell(arch: ArchConfig, shape: ShapeSpec, smoke: bool = False) -> Cell:
    base_cfg: G.GNNConfig = arch.smoke_model if smoke else arch.model
    d_feat = 8 if smoke else shape["d_feat"]
    cfg = dataclasses.replace(base_cfg, d_node_in=d_feat)

    if shape.kind == "train_sampled":
        n_pad, e_pad = (64, 80) if smoke else edge_budget(
            shape["batch_nodes"], (shape["fanout0"], shape["fanout1"]))
        graph = {
            "nodes": jax.ShapeDtypeStruct((n_pad, d_feat), jnp.float32),
            "edges": jax.ShapeDtypeStruct((e_pad, cfg.d_edge_in), jnp.float32),
            "senders": jax.ShapeDtypeStruct((e_pad,), jnp.int32),
            "receivers": jax.ShapeDtypeStruct((e_pad,), jnp.int32),
            "edge_mask": jax.ShapeDtypeStruct((e_pad,), jnp.bool_),
        }
        batch_in = {
            "graph": graph,
            "targets": jax.ShapeDtypeStruct((n_pad, cfg.d_out), jnp.float32),
            "node_mask": jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        }
        n_edges_step = e_pad
    elif shape.kind == "train_batched":
        bsz = 4 if smoke else shape["batch"]
        nn, ne = (8, 12) if smoke else (shape["n_nodes"], shape["n_edges"])
        graph = {
            "nodes": jax.ShapeDtypeStruct((bsz, nn, d_feat), jnp.float32),
            "edges": jax.ShapeDtypeStruct((bsz, ne, cfg.d_edge_in), jnp.float32),
            "senders": jax.ShapeDtypeStruct((bsz, ne), jnp.int32),
            "receivers": jax.ShapeDtypeStruct((bsz, ne), jnp.int32),
        }
        batch_in = {
            "graph": graph,
            "targets": jax.ShapeDtypeStruct((bsz, nn, cfg.d_out), jnp.float32),
        }
        n_edges_step = bsz * ne
    else:  # full-batch train
        nn, ne = (32, 128) if smoke else (shape["n_nodes"], shape["n_edges"])
        ne_pad = _round_up(ne, 512 * 256)  # edge shards over the whole mesh
        graph = {
            "nodes": jax.ShapeDtypeStruct((nn, d_feat), jnp.float32),
            "edges": jax.ShapeDtypeStruct((ne_pad, cfg.d_edge_in), jnp.float32),
            "senders": jax.ShapeDtypeStruct((ne_pad,), jnp.int32),
            "receivers": jax.ShapeDtypeStruct((ne_pad,), jnp.int32),
            "edge_mask": jax.ShapeDtypeStruct((ne_pad,), jnp.bool_),
        }
        batch_in = {
            "graph": graph,
            "targets": jax.ShapeDtypeStruct((nn, cfg.d_out), jnp.float32),
            "node_mask": jax.ShapeDtypeStruct((nn,), jnp.float32),
        }
        n_edges_step = ne_pad

    params_shape = jax.eval_shape(lambda k: G.init(k, cfg), jax.random.key(0))
    pspecs = _replicated(params_shape)  # GNN MLPs are tiny -> replicate
    opt_cfg = AdamWConfig(lr=1e-3, moment_dtype=arch.train_moment_dtype)
    opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_shape)
    ospecs = _replicated(opt_shape)

    e_ax = resolve(("edges",))[0] if _mesh() else None
    bspecs = jax.tree.map(lambda _: P(), batch_in)
    if _mesh():
        edge_spec = P(e_ax)
        g = dict(bspecs["graph"])
        for k in ("edges", "senders", "receivers", "edge_mask"):
            if k in g:
                g[k] = P(e_ax, *([None] * (len(batch_in["graph"][k].shape) - 1)))
        if shape.kind == "train_batched":
            b_ax = resolve(("batch",))[0]
            g = {k: P(b_ax, *([None] * (len(v.shape) - 1)))
                 for k, v in batch_in["graph"].items()}
            bspecs = {"graph": g, "targets": P(b_ax, None, None)}
        else:
            bspecs = dict(bspecs)
            bspecs["graph"] = g

    def train_step(params, opt_state, batch_):
        (loss, metrics), grads = jax.value_and_grad(
            G.loss_fn, has_aux=True)(params, cfg, batch_)
        updates, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **metrics}

    n_params = cfg.params_count()
    meta = {
        "family": "gnn", "params_total": n_params, "params_active": n_params,
        "edges": n_edges_step,
        # per MP layer: edge MLP (3h->h->h) + node MLP (2h->h->h) matmuls
        "model_flops": 6 * n_edges_step * cfg.n_layers
        * (3 * cfg.d_hidden * cfg.d_hidden + cfg.d_hidden * cfg.d_hidden) * 2,
        "train": True,
    }
    return Cell(
        arch.arch_id, shape.name, shape.kind, train_step,
        (params_shape, opt_shape, batch_in),
        (pspecs, ospecs, bspecs),
        (pspecs, ospecs, {"loss": P(), "mse": P()}),
        meta,
    )


# ========================================================== recsys cells
def build_recsys_cell(arch: ArchConfig, shape: ShapeSpec, smoke: bool = False) -> Cell:
    cfg: R.RecsysConfig = arch.smoke_model if smoke else arch.model
    batch = 8 if smoke else shape["batch"]

    params_shape = jax.eval_shape(lambda k: R.init(k, cfg), jax.random.key(0))
    pspecs = sanitize_tree(params_shape, R.param_specs(cfg), _mesh()) if _mesh() else _replicated(params_shape)

    def batch_inputs():
        b_ax = resolve(("batch",))[0] if _mesh() else None
        if cfg.kind == "dlrm":
            ins = {
                "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
                "sparse": jax.ShapeDtypeStruct((batch, cfg.n_sparse), jnp.int32),
                "label": jax.ShapeDtypeStruct((batch,), jnp.float32),
            }
        elif cfg.kind == "two_tower":
            ins = {
                "user": jax.ShapeDtypeStruct((batch,), jnp.int32),
                "item": jax.ShapeDtypeStruct((batch,), jnp.int32),
            }
        elif cfg.kind == "bst":
            ins = {
                "seq": jax.ShapeDtypeStruct((batch, cfg.seq_len), jnp.int32),
                "target": jax.ShapeDtypeStruct((batch,), jnp.int32),
                "label": jax.ShapeDtypeStruct((batch,), jnp.float32),
            }
        else:  # wide_deep
            ins = {
                "sparse": jax.ShapeDtypeStruct((batch, cfg.n_sparse), jnp.int32),
                "label": jax.ShapeDtypeStruct((batch,), jnp.float32),
            }
        specs = {k: P(b_ax, *([None] * (len(v.shape) - 1))) for k, v in ins.items()}
        return ins, specs

    n_params = cfg.params_count()
    meta = {
        "family": "recsys", "params_total": n_params, "params_active": n_params,
        "batch": batch, "train": shape.kind == "train",
        "model_flops": _recsys_flops(cfg, batch, shape.kind),
    }

    if shape.kind == "train":
        opt_cfg = AdamWConfig(lr=1e-3, moment_dtype=arch.train_moment_dtype)
        opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_shape)
        ospecs = _opt_specs(pspecs, opt_shape) if _mesh() else _replicated(opt_shape)
        ins, bspecs = batch_inputs()

        def train_step(params, opt_state, batch_):
            (loss, metrics), grads = jax.value_and_grad(
                R.loss_fn, has_aux=True)(params, cfg, batch_)
            updates, opt_state = adamw_update(grads, opt_state, params, opt_cfg)
            params = apply_updates(params, updates)
            return params, opt_state, {"loss": loss, **metrics}

        mkeys = {"two_tower": "nll"}.get(cfg.kind, "bce")
        return Cell(
            arch.arch_id, shape.name, shape.kind, train_step,
            (params_shape, opt_shape, ins),
            (pspecs, ospecs, bspecs),
            (pspecs, ospecs, {"loss": P(), mkeys: P()}),
            meta,
        )

    if shape.kind == "serve":
        ins, bspecs = batch_inputs()

        def serve_step(params, batch_):
            return R.serve_scores(params, cfg, batch_)

        b_ax = resolve(("batch",))[0] if _mesh() else None
        return Cell(
            arch.arch_id, shape.name, shape.kind, serve_step,
            (params_shape, ins), (pspecs, bspecs), P(b_ax), meta,
        )

    # retrieval: 1 query vs n_candidates — the paper's FD-SQ dataflow
    n_cand = 4096 if smoke else shape["n_candidates"]
    d_out = cfg.tower_mlp[-1] if cfg.kind == "two_tower" else cfg.embed_dim
    k = 16 if smoke else 100
    cand = jax.ShapeDtypeStruct((n_cand, d_out), jnp.float32)
    uid = jax.ShapeDtypeStruct((batch,), jnp.int32)
    rows_ax = resolve(("rows",))[0] if _mesh() else None

    if cfg.kind == "two_tower":
        def retrieve(params, user_ids, candidates):
            return R.retrieve_topk(params, cfg, user_ids, candidates, k)
    else:
        # pointwise models score candidate id lists exhaustively: treat the
        # candidate matrix as precomputed item representations and rank by
        # inner product against the pooled user state (generic fallback).
        def retrieve(params, user_ids, candidates):
            from repro.core.fqsd import chunk_step
            from repro.core.topk import empty_topk
            u = R.embedding_lookup(params["embed"], user_ids + 0)
            if u.shape[-1] != candidates.shape[-1]:
                u = jnp.pad(u, ((0, 0), (0, candidates.shape[-1] - u.shape[-1])))
            state = empty_topk((u.shape[0],), k)
            return chunk_step(state, u, candidates, None, 0, candidates.shape[0], "ip")

    meta = dict(meta)
    meta["model_flops"] = 2 * batch * n_cand * d_out
    meta["n_candidates"] = n_cand
    from repro.core.topk import TopK
    out_sp = TopK(P(), P())
    return Cell(
        arch.arch_id, shape.name, shape.kind, retrieve,
        (params_shape, uid, cand),
        (pspecs, P(None), P(rows_ax, None)),
        out_sp, meta,
    )


def _recsys_flops(cfg: R.RecsysConfig, batch: int, kind: str) -> int:
    def mlp_f(dims):
        return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    if cfg.kind == "dlrm":
        f = mlp_f((cfg.n_dense,) + cfg.bot_mlp)
        n_int = cfg.n_sparse + 1
        f += 2 * n_int * n_int * cfg.embed_dim
        f += mlp_f((n_int * (n_int - 1) // 2 + cfg.bot_mlp[-1],) + cfg.top_mlp)
    elif cfg.kind == "two_tower":
        f = 2 * mlp_f((cfg.embed_dim,) + cfg.tower_mlp)
    elif cfg.kind == "bst":
        d = cfg.embed_dim
        f = 2 * cfg.seq_len * (4 * d * d) + 2 * cfg.seq_len * cfg.seq_len * d
        f += mlp_f((2 * d,) + cfg.top_mlp + (1,))
    else:
        f = mlp_f((cfg.n_sparse * cfg.embed_dim,) + cfg.top_mlp + (1,))
    per_example = f + 2 * cfg.n_sparse * cfg.embed_dim  # lookups
    mult = 3 if kind == "train" else 1  # fwd+bwd
    return batch * per_example * mult


# ============================================================== kNN cells
def build_knn_cell(arch: ArchConfig, shape: ShapeSpec, smoke: bool = False) -> Cell:
    from repro.core import sharded as S

    if smoke:
        n, d, m, k = 2048, 128, 4, 16
    else:
        n, d, m, k = shape["n"], shape["d"], shape["m"], shape["k"]
    d_pad = _round_up(d, 128)
    mesh = _mesh()
    total = 256
    if mesh:
        total = int(np.prod(list(mesh.axis_sizes)))
    n_pad = _round_up(n, 128 * total)

    vec = jax.ShapeDtypeStruct((n_pad, d_pad), jnp.float32)
    nrm = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
    q = jax.ShapeDtypeStruct((m, d_pad), jnp.float32)

    data_axes = ("data", "model") if not mesh or "pod" not in mesh.axis_names \
        else ("pod", "data", "model")
    # queries shard over `data` only (the executors' shard_map contract);
    # sanitized for small m (e.g. GIST m=16 on the multi-pod mesh).
    q_sp = _san((m, d_pad), P("data", None))
    q_ax = q_sp[0] if _mesh() else None

    if shape.kind == "knn_fdsq":
        def fn(qv, vecs, norms):
            if _mesh() is None:
                from repro.core.fdsq import fdsq_search
                return fdsq_search(qv, vecs, norms, k, "l2", 4)
            return S.fdsq_sharded(_mesh(), k, "l2", data_axes,
                                  chunk_rows=None)(qv, vecs, norms)
        in_specs = (P(), P(data_axes), P(data_axes))
        from repro.core.topk import TopK
        out_specs = TopK(P(), P())
    elif shape.kind in ("knn_ring", "knn_ring_q"):
        ring = S.fqsd_ring_queries if shape.kind == "knn_ring_q" else S.fqsd_ring

        def fn(qv, vecs, norms):
            if _mesh() is None:
                from repro.core.fqsd import fqsd_scan
                return fqsd_scan(qv, vecs, norms, k, "l2", 256)
            return ring(_mesh(), k, "l2", "data", "model")(qv, vecs, norms)
        in_specs = (P(q_ax), P(("data", "model")), P(("data", "model")))
        from repro.core.topk import TopK
        out_specs = TopK(P(q_ax), P(q_ax))
    else:  # knn_fqsd
        def fn(qv, vecs, norms):
            if _mesh() is None:
                from repro.core.fqsd import fqsd_scan
                return fqsd_scan(qv, vecs, norms, k, "l2", 256)
            return S.fqsd_sharded(_mesh(), k, "l2", "data", "model")(qv, vecs, norms)
        in_specs = (P(q_ax), P("model"), P("model"))
        from repro.core.topk import TopK
        out_specs = TopK(P(q_ax), P(q_ax))

    meta = {
        "family": "knn", "params_total": 0, "params_active": 0,
        "model_flops": 2 * m * n * d + m * n,  # GEMM + epilogue
        "n": n, "d": d, "m": m, "k": k, "train": False,
        "dataset_bytes": n_pad * d_pad * 4,
    }
    return Cell(arch.arch_id, shape.name, shape.kind, fn,
                (q, vec, nrm), in_specs, out_specs, meta)


def _round_up(v, m):
    return ((v + m - 1) // m) * m


# ================================================================ dispatch
def build_cell(arch: ArchConfig, shape: ShapeSpec, smoke: bool = False) -> Cell:
    if arch.family == "lm":
        return build_lm_cell(arch, shape, smoke)
    if arch.family == "gnn":
        return build_gnn_cell(arch, shape, smoke)
    if arch.family == "recsys":
        return build_recsys_cell(arch, shape, smoke)
    if arch.family == "knn":
        return build_knn_cell(arch, shape, smoke)
    raise ValueError(arch.family)
