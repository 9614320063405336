"""Multi-collection Router — named DatasetStore-backed engines, one cache.

A production retrieval service rarely serves one corpus: the Router maps a
collection name to a :class:`~repro.core.engine.ExactKNN` engine (each
backed by its own :class:`~repro.store.DatasetStore`) and routes
:class:`~repro.api.types.SearchRequest` traffic by name. All collections
share the process-wide **bounded executable cache** (the paper's single
physical "bitstream"): plans are keyed by shapes + options, not by
collection, so two collections with identical geometry reuse each other's
compiled executables, and interleaving mode switches and store mutations
across collections never recompiles for seen shapes (see
tests/test_router.py).

Per-collection stats (requests, queries, bytes scanned per tier) make the
multi-tenant traffic picture visible; :meth:`cache_info` exposes the shared
cache so the no-reflashing invariant stays observable in serving.

Core imports are deliberately lazy: ``repro.api`` must be importable from
``repro.core.engine`` (which imports the request/result types) without a
cycle.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

from repro.api.types import SearchRequest, SearchResult

#: one Mesh per device group (keyed by device ids): collections placed on
#: the same devices share the identical Mesh object, so their mesh
#: executables share cache entries by construction
_MESH_BY_GROUP: dict = {}


class Router:
    """Route search traffic across named collections.

    Usage:
        router = Router()
        router.create("passages", corpus, k=10, metric="ip")
        router.attach("images", prebuilt_engine)
        res = router.search("passages", SearchRequest(queries=q, k=5))
        router.stats()        # per-collection traffic + shared cache info

    ``executable_cache_entries`` bounds the shared compiled-executable LRU
    (None keeps the current process-wide limit untouched).
    """

    def __init__(self, executable_cache_entries: int | None = None):
        if executable_cache_entries is not None:
            from repro.core.executors import set_executable_cache_limit

            set_executable_cache_limit(executable_cache_entries)
        self._engines: dict[str, object] = {}
        self._stats: dict[str, dict] = {}

    # ----------------------------------------------------------- collections
    def create(self, name: str, vectors=None, *, store=None, devices=None,
               **engine_kwargs):
        """Build and attach a DatasetStore-backed engine for `name`.

        Pass either raw ``vectors`` (an (N, d) array; wrapped in an
        in-memory store) or a prebuilt ``store`` (possibly mmap-backed /
        multi-shard). Remaining kwargs go to the ``ExactKNN`` constructor
        (k, metric, backend, device_budget_bytes, ...).

        ``devices`` places the collection's shards across a device group:
        pass a device count (first N local devices) or an explicit sequence
        of ``jax.Device``. The Router builds a 1-D ``("data",)`` mesh over
        them and hands it to the engine, so resident tiers shard row-wise
        across the group and streamed tiers ring-stream over it — while the
        process-wide executable cache stays shared: two collections placed
        on the same device group reuse each other's compiled mesh
        executables (same ``(cache_key, mesh, axes)``).
        """
        from repro.core.engine import ExactKNN

        self._check_name(name)  # fail before any fitting/device work
        if (vectors is None) == (store is None):
            raise ValueError("pass exactly one of `vectors` or `store`")
        if devices is not None:
            if "mesh" in engine_kwargs:
                raise ValueError("pass either `devices` or `mesh`, not both")
            engine_kwargs = dict(
                engine_kwargs,
                mesh=self._make_mesh(devices),
                mesh_axes=("data",),
            )
        engine = ExactKNN(**engine_kwargs)
        if store is not None:
            engine.fit_store(store)
        else:
            engine.fit(np.asarray(vectors, dtype=np.float32))
        return self.attach(name, engine)

    @staticmethod
    def _make_mesh(devices):
        """A 1-D ``("data",)`` mesh over an explicit device group.

        ``devices`` is a count (first N of ``jax.devices()``) or a sequence
        of ``jax.Device``. The same group always yields an identical mesh,
        keeping the shared-cache key ``(plan.cache_key(), mesh, axes)``
        stable across collections placed on the same devices.
        """
        import jax

        if isinstance(devices, int):
            avail = jax.devices()
            if not 1 <= devices <= len(avail):
                raise ValueError(
                    f"devices={devices} but {len(avail)} device(s) present"
                )
            devices = avail[:devices]
        devices = list(devices)
        if not devices:
            raise ValueError("`devices` must name at least one device")
        group = tuple(d.id for d in devices)
        if group not in _MESH_BY_GROUP:
            _MESH_BY_GROUP[group] = jax.make_mesh(
                (len(devices),), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,), devices=devices,
            )
        return _MESH_BY_GROUP[group]

    def _check_name(self, name: str) -> None:
        if not isinstance(name, str) or not name:
            raise ValueError(f"collection name must be a non-empty str, got {name!r}")
        if name in self._engines:
            raise ValueError(f"collection {name!r} already exists")

    def attach(self, name: str, engine):
        """Attach an already-fitted engine under `name`."""
        self._check_name(name)
        if not engine.is_fitted:
            raise ValueError(f"engine for collection {name!r} must be fitted")
        mesh = getattr(engine, "mesh", None)
        self._engines[name] = engine
        self._stats[name] = {
            "requests": 0,
            "queries": 0,
            "bytes_scanned": {"f32": 0, "int8": 0},
            "tiers": set(),
            "devices": ([str(d) for d in mesh.devices.flat]
                        if mesh is not None else None),
        }
        return engine

    def drop(self, name: str) -> None:
        """Detach a collection (compiled executables stay cached — they are
        keyed by shapes, and another collection may share them)."""
        self.engine(name)  # raise the uniform KeyError on unknown names
        del self._engines[name]
        del self._stats[name]

    def engine(self, name: str):
        """The engine behind `name` (for fitting-time ops: enable_int8...)."""
        try:
            return self._engines[name]
        except KeyError:
            raise KeyError(
                f"unknown collection {name!r}; known: {self.collections()}"
            ) from None

    def collections(self) -> tuple:
        return tuple(sorted(self._engines))

    def __contains__(self, name: str) -> bool:
        return name in self._engines

    def __len__(self) -> int:
        return len(self._engines)

    def __iter__(self) -> Iterator[str]:
        return iter(self.collections())

    # ------------------------------------------------------------- traffic
    def search(self, collection: str, request: SearchRequest) -> SearchResult:
        """Serve one request against the named collection."""
        result = self.engine(collection).search(request)
        s = self._stats[collection]
        s["requests"] += 1
        s["queries"] += int(result.stats.get("m", 1))
        s["bytes_scanned"][result.tier] = (
            s["bytes_scanned"].get(result.tier, 0)
            + int(result.stats.get("bytes_scanned", 0))
        )
        s["tiers"].add(result.tier)
        return result

    def upsert(self, collection: str, vectors) -> np.ndarray:
        """Append rows to the named collection (visible to the next
        request; never recompiles for seen shapes)."""
        return self.engine(collection).upsert(vectors)

    def delete(self, collection: str, ids) -> None:
        """Tombstone rows of the named collection by global id."""
        self.engine(collection).delete(ids)

    # ----------------------------------------------------------- compaction
    def compact(self, collection: str, wait: bool = False) -> dict:
        """Fold the named collection's delta rows + tombstones into a fresh
        store generation (atomic pointer swap; searches never block — see
        ``DatasetStore.compact``). ``wait=False`` triggers the store's
        background compactor and returns immediately; ``wait=True`` runs it
        synchronously (tests, admin tooling). Returns the collection's
        compaction status after the trigger."""
        store = self._compactable_store(collection)
        if wait:
            store.compact()
        else:
            store.compact_async()
        return self.compaction_status(collection)

    def compaction_status(self, collection: str) -> dict:
        """Live compaction/generation state of the named collection."""
        return self._compactable_store(collection).compaction_status()

    def _compactable_store(self, collection: str):
        eng = self.engine(collection)
        store = getattr(eng, "store", None)
        if store is None or not hasattr(store, "compact"):
            raise ValueError(
                f"collection {collection!r} is not backed by a compactable "
                f"DatasetStore"
            )
        return store

    # --------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Per-collection traffic + the shared executable cache counters.
        ``queries`` counts engine rows per dispatch — a batch the scheduler
        bucket-padded to a power of two counts its padded size."""
        out = {}
        for name in self.collections():
            s = self._stats[name]
            store = getattr(self._engines[name], "store", None)
            out[name] = {
                "requests": s["requests"],
                "queries": s["queries"],
                "bytes_scanned": dict(s["bytes_scanned"]),
                "tiers": sorted(s["tiers"]),
                "n_rows": int(self._engines[name].n),
                "devices": s["devices"],
                "compaction": (store.compaction_status()
                               if hasattr(store, "compaction_status")
                               else None),
            }
        return {"collections": out, "executable_cache": self.cache_info()}

    def cache_info(self) -> dict:
        """The shared executable cache (hits/misses/evictions/size) — the
        router-level view of the no-reflashing invariant."""
        from repro.core.executors import cache_info

        return cache_info()


__all__ = ["Router"]
