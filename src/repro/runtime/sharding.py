"""Sharding rules — logical axis names resolved against the active mesh.

Model code annotates tensors with LOGICAL axes ("batch", "seq", "embed",
"heads", "ffn", "expert", "vocab", "rows", "edges", ...). The rules map
logical axes to mesh axes; anything unmapped is replicated. On a meshless
CPU test run every constraint is a no-op, so the same model code serves
smoke tests, training, and the multi-pod dry-run.

Default rules target the production mesh (pod, data, model):
    batch  -> (pod, data)     activations/data parallel
    embed  -> model  (FSDP param shard: weights gather per-layer)
    heads/ffn/expert/vocab/rows -> model   (tensor/expert/table parallel)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import PartitionSpec as P


# logical axis -> mesh axis (or tuple of mesh axes)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "batch_nopod": "data",
    "seq": None,
    "embed": None,  # replicated activations along d_model
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "expert": "model",
    "vocab": "model",
    "rows": "model",  # embedding-table / dataset rows
    "fsdp": ("pod", "data"),  # parameter sharding axis for ZeRO-3
    "edges": ("pod", "data", "model"),  # GNN edge partitions
    "nodes": None,
}

_local = threading.local()


def current_rules() -> Mapping[str, object]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: Mapping[str, object]):
    prev = getattr(_local, "rules", DEFAULT_RULES)
    _local.rules = dict(rules)
    try:
        yield
    finally:
        _local.rules = prev


def _mesh_axis_names() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def resolve(logical: Sequence[str | None]) -> P:
    """Translate logical axes to a PartitionSpec under the current rules,
    dropping mesh axes that do not exist on the active mesh."""
    names = _mesh_axis_names()
    rules = current_rules()
    out = []
    for ax in logical:
        if ax is None:
            out.append(None)
            continue
        tgt = rules.get(ax)
        if tgt is None:
            out.append(None)
        elif isinstance(tgt, tuple):
            present = tuple(t for t in tgt if t in names)
            out.append(present if len(present) > 1 else (present[0] if present else None))
        else:
            out.append(tgt if tgt in names else None)
    return P(*out)


def _mesh_axis_sizes() -> Mapping[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _axis_product(entry, sizes: Mapping[str, int]) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        p = 1
        for e in entry:
            p *= sizes.get(e, 1)
        return p
    return sizes.get(entry, 1)


def sanitize_spec(shape: Sequence[int], spec: P, sizes: Mapping[str, int] | None = None) -> P:
    """Drop spec axes whose mesh-size does not divide the dim evenly.

    jit in_shardings rejects uneven shards (XLA pads only through
    with_sharding_constraint), so e.g. minicpm's odd vocab=122753 falls back
    to replicated on that dim. Starcoder2's 36 heads similarly drop the
    16-way head axis at the activation level.
    """
    sizes = _mesh_axis_sizes() if sizes is None else sizes
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        out.append(entry if dim % _axis_product(entry, sizes) == 0 else None)
    return P(*out)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh;
    drops axes that do not divide the dim (uneven shards)."""
    if not _mesh_axis_names():
        return x
    sp = sanitize_spec(x.shape, resolve(logical))
    return jax.lax.with_sharding_constraint(x, sp)


def spec(*logical: str | None) -> P:
    """PartitionSpec for in_shardings/out_shardings construction."""
    return resolve(logical)


def sanitize_tree(shapes_tree, specs_tree, mesh: jax.sharding.Mesh):
    """Per-leaf sanitize_spec over a (ShapeDtypeStruct tree, spec tree) pair.

    specs_tree leaves must be PartitionSpec; shapes_tree leads the map so
    spec subtrees may be shared/broadcast (e.g. one layer-spec dict against
    stacked layer params).
    """
    sizes = {a: mesh.shape[a] for a in mesh.axis_names}
    return jax.tree.map(
        lambda s, sp: sanitize_spec(s.shape, sp, sizes), shapes_tree, specs_tree
    )
