"""ExactKNN — thin facade over the store + planner/executor core.

Architecture (one PR of the paper's fig. 1 / fig. 2 made explicit):

    ExactKNN (this module)          facade: owns config + device views only
        -> repro.store.DatasetStore dataset layer: manifest of tiered shards
                                    (f32 / int8, in-memory or mmap files),
                                    online upsert/delete (delta + tombstones)
        -> planner.plan(...)        PURE: shapes + store meta -> ExecutionPlan
        -> executors.execute(...)   registry: plan -> compiled executable
             fdsq-xla / fqsd-xla / fdsq-pallas / fqsd-streamed /
             fqsd-mmap-streamed / fqsd-int8 / fqsd-int8-pallas /
             fqsd-int8-streamed / fqsd-int8-mmap-streamed /
             fdsq-sharded / fqsd-sharded
        -> serving.AdaptiveScheduler   picks FD-SQ vs FQ-SD plans per batch,
                                       routes deep backlogs to the int8 tier

One engine object plays the role of the single physical FPGA configuration:
FD-SQ and FQ-SD are *logical* configurations over the same compiled building
blocks, and the executor layer caches every compiled executable keyed by
plan, so switching modes at run time never recompiles for shapes already
seen — the paper's "no reflashing" invariant (section 3.2), testable via
``repro.core.executors.cache_info()``. Dataset mutations preserve it too:
tombstones ride the norms channel (runtime data, not shapes) and upserts
land in fixed-geometry delta shards.

Usage (request-first API — every option is a per-request fact):
    eng = ExactKNN(k=10, metric="l2")
    eng.fit(dataset)                       # FD-SQ: resident dataset
    res = eng.search(SearchRequest(queries=q))            # auto mode
    res = eng.search(SearchRequest(queries=Q, mode_hint="fqsd"))
    res = eng.search(SearchRequest(queries=Q, k=3))       # per-request k
    eng.enable_int8()
    res = eng.search(SearchRequest(queries=Q, tier="int8"))
    res.topk, res.certified, res.plan, res.kernel_stats   # one result type
    ids = eng.upsert(new_rows)             # visible to the next request
    eng.delete(ids[:1])                    # ditto; still exact
    eng.plans                              # every ExecutionPlan executed

The historical entry points (``query``, ``query_batch``,
``query_batch_int8``, ``query_stream``, ``search_streamed``) remain as thin
deprecated shims over :meth:`search`.

Out-of-core: ``ExactKNN(..., device_budget_bytes=B).fit_store(store)`` with
an mmap-backed store bigger than B routes every request through the
manifest-driven streamed executor. Distributed (mesh) usage routes to the
sharded executors; Pallas-fused kernels are selected with backend="pallas".
Mode selection itself lives in ``repro.core.planner`` — this class contains
no ``if mesh`` / ``if backend`` dispatch of its own.
"""
from __future__ import annotations

import time
import warnings
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as part
from repro.core import sharded as sh
from repro.core.distance import Metric, validate_metric
from repro.core.executors import (
    ExecContext,
    MeshTiered,
    TieredResident,
    cached_partition_step,
    execute,
)
from repro.core.planner import (
    Backend,
    DatasetMeta,
    EngineConfig,
    EnginePlan,
    ExecutionPlan,
    plan as plan_fn,
)
from repro.core.quantized import QuantizedDataset
from repro.core.topk import TopK
from repro.api.types import AUTO_FDSQ_MAX_BATCH, SearchRequest, SearchResult


def _deprecated_shim(old: str, new: str) -> None:
    warnings.warn(
        f"ExactKNN.{old} is deprecated; use ExactKNN.search("
        f"SearchRequest({new})) instead (see docs/api.md)",
        DeprecationWarning, stacklevel=3,
    )


def _keep_rows(mask: np.ndarray, base_index: int, n_valid: int,
               n_pad: int) -> np.ndarray:
    """Slice a global-id filter mask down to one padded row block: True =
    row eligible; padding rows stay True (their norms are +inf already).
    The ONE place the id-space -> row-block arithmetic lives — every mask
    fold (resident f32, int8 norms_sq, delta shards, streamed shards) goes
    through here so the semantics cannot drift between paths."""
    keep = np.ones(n_pad, dtype=bool)
    keep[:n_valid] = mask[base_index : base_index + n_valid]
    return keep


class _MaskedShardSource:
    """A DatasetStore view with a per-request filter mask folded onto each
    shard's validity channel as it streams (+inf norm on f32 shards, +inf
    quantized norm on int8 partitions, +inf norm on delta shards) —
    duck-types the store surface the streamed executors read
    (``iter_shards`` / ``shard_source`` / ``delta_shards`` /
    ``gather_rows``)."""

    def __init__(self, store, mask: np.ndarray):
        self._store = store
        self._mask = mask

    def _fold_f32(self, p: part.PaddedDataset) -> part.PaddedDataset:
        keep = _keep_rows(self._mask, p.base_index, p.n_valid,
                          int(p.vectors.shape[0]))
        if keep.all():
            return p
        norms = np.where(keep, np.asarray(p.norms), np.float32(np.inf))
        return part.PaddedDataset(p.vectors, norms.astype(np.float32),
                                  p.n_valid, p.base_index)

    def _fold_int8(self, p):
        keep = _keep_rows(self._mask, p.base_index, p.n_valid,
                          int(p.qnorm.shape[0]))
        if keep.all():
            return p
        qnorm = np.where(keep, np.asarray(p.qnorm), np.float32(np.inf))
        return p._replace(qnorm=qnorm.astype(np.float32))

    def iter_shards(self, tier: str = "f32"):
        if tier == "f32":
            for p in self._store.iter_shards():
                yield self._fold_f32(p)
            return
        for p in self._store.iter_shards(tier):
            yield self._fold_int8(p)

    def read_shard(self, i: int, tier: str = "f32"):
        # resilience surface: forwards to the store's fault-hooked read
        # (retry / CRC / quarantine live below), folding the mask onto the
        # returned partition. Quarantine means an int8 request can come
        # back as an f32 PaddedDataset — fold by the returned type.
        p = self._store.read_shard(i, tier)
        return (self._fold_f32(p) if isinstance(p, part.PaddedDataset)
                else self._fold_int8(p))

    def shard_source(self, tier: str = "f32"):
        return _MaskedTierSource(self, tier)

    def delta_shards(self):
        return [self._fold_f32(p) for p in self._store.delta_shards()]

    def gather_rows(self, ids) -> np.ndarray:
        # candidate indices already passed the masked scan: excluded rows
        # carry +inf bounds / index -1, so no mask re-check is needed here.
        # Thread-safe like the store's own gather (pure numpy/memmap reads)
        # — the speculative gather thread calls this mid-scan.
        return self._store.gather_rows(ids)

    @property
    def n_shards(self) -> int:
        # the streamed executors size their speculation trigger by shard
        # count; masking never changes the shard layout
        return self._store.n_shards


class _MaskedTierSource:
    """Restartable iterable over one tier of a masked shard source."""

    def __init__(self, source: _MaskedShardSource, tier: str):
        self._source = source
        self._tier = tier

    def __iter__(self):
        return self._source.iter_shards(self._tier)


class ExactKNN:
    def __init__(
        self,
        k: int,
        metric: Metric = "l2",
        backend: Backend = "xla",
        chunk_rows: int = 8192,
        n_partitions: int = 8,
        mesh: jax.sharding.Mesh | None = None,
        mesh_axes: Sequence[str] = ("data", "model"),
        dtype=jnp.float32,
        rescore_factor: int | None = None,
        device_budget_bytes: int | None = None,
        prefetch_depth: int | None = None,
        spec_trigger: float | None = None,
        max_retries: int | None = None,
        retry_backoff_s: float | None = None,
    ):
        validate_metric(metric)
        if k < 1:
            raise ValueError("k must be >= 1")
        if prefetch_depth is not None and prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got {prefetch_depth}")
        if max_retries is not None and max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if retry_backoff_s is not None and retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {retry_backoff_s}"
            )
        if spec_trigger is not None and not (0.0 <= spec_trigger <= 1.0):
            raise ValueError(
                "spec_trigger must be a shard fraction in [0, 1] "
                f"(1 disables speculation), got {spec_trigger}"
            )
        if rescore_factor is not None and rescore_factor < 1:
            raise ValueError(
                f"rescore_factor must be >= 1, got {rescore_factor}"
            )
        self.k = int(k)
        self.metric = metric
        self.backend: Backend = backend
        self.chunk_rows = int(chunk_rows)
        self.n_partitions = int(n_partitions)
        self.mesh = mesh
        self.mesh_axes = tuple(mesh_axes)
        self.dtype = dtype
        #: int8 exact-rescore budget (x k). None = default 4, and the
        #: pipeline autotuner may override it per plan; an explicit value
        #: is PINNED — tuning never overrides a caller's budget.
        self.rescore_factor = 4 if rescore_factor is None else int(rescore_factor)
        self._rescore_pinned = rescore_factor is not None
        self.device_budget_bytes = device_budget_bytes
        #: streamed-scan double-buffer depth (2 = the paper's two memory
        #: banks; deeper trades host memory for jitter tolerance). Threaded
        #: into every ExecContext — launch/serve.py exposes --prefetch-depth.
        #: None = default 2, overridable by a tuned plan; explicit = pinned.
        self.prefetch_depth = 2 if prefetch_depth is None else int(prefetch_depth)
        self._prefetch_pinned = prefetch_depth is not None
        #: streamed-int8 speculation trigger (shard fraction after which
        #: the candidate gather starts on a background thread; 1.0 = no
        #: speculation). None = tuned plan value, else the executor default.
        self.spec_trigger = spec_trigger
        #: bounded retry budget for streamed shard reads / candidate gathers
        #: / device transfers (exponential backoff from retry_backoff_s) —
        #: a SearchRequest.max_retries overrides it per request.
        self.max_retries = 2 if max_retries is None else int(max_retries)
        self.retry_backoff_s = (0.05 if retry_backoff_s is None
                                else float(retry_backoff_s))
        self._store = None  # repro.store.DatasetStore
        self._resident = True
        # cos + fused backend: the resident view is normalized at fit time
        # (every resident plan routes to the fused kernel, which then skips
        # its own dataset normalization; delta/streamed paths score raw
        # rows through cosine_distance, which is scale-invariant anyway)
        self._cos_prenormalized = (
            metric == "cos" and backend == "pallas" and mesh is None
        )
        self._ds: part.PaddedDataset | None = None  # device f32 view
        self._int8: QuantizedDataset | None = None  # device int8 view
        self._delta_dev: list[part.PaddedDataset] = []  # device delta shards
        self._seen_mutations = 0
        self._seen_generation = 0  # store generation the device views mirror
        self._plans: list[ExecutionPlan] = []
        self._last_ctx: ExecContext | None = None

    # ------------------------------------------------------------------ fit
    def fit(self, vectors: np.ndarray | jax.Array) -> "ExactKNN":
        """Load the dataset device-resident (FD-SQ, fig. 2 arrow 1).

        Thin wrapper: builds a single-shard in-memory DatasetStore and
        attaches it. Use :meth:`fit_store` to attach a prebuilt (possibly
        mmap-backed, multi-shard, multi-tier) store directly.
        """
        from repro.store import DatasetStore

        v = np.asarray(vectors, dtype=np.float32)
        if v.ndim != 2:
            raise ValueError(f"expected (N, d) dataset, got {v.shape}")
        store = DatasetStore.from_array(v, row_mult=self._row_mult(v.shape[0]))
        return self.fit_store(store)

    def fit_store(self, store, resident: bool | None = None) -> "ExactKNN":
        """Attach a DatasetStore. Residency: explicit `resident` flag, else
        the store's f32 bytes vs `device_budget_bytes` (None = unlimited).
        Non-resident stores serve every query through the manifest-driven
        streamed executor (fqsd-mmap-streamed)."""
        if resident is None:
            budget = self.device_budget_bytes
            resident = budget is None or store.nbytes("f32") <= budget
        self._store = store
        self._resident = bool(resident)
        self._ds = None
        self._int8 = None
        self._delta_dev = []
        self._seen_generation = getattr(store, "generation", 0)
        self._seen_mutations = store.mutation_count
        if self._resident:
            host = store.resident()  # tombstones already folded into norms
            if self.mesh is not None:
                # straight from host to each device's rows: staging the
                # whole corpus on one device first would not fit once the
                # corpus is larger than one chip
                vec, nrm = sh.shard_dataset(
                    self.mesh, np.asarray(host.vectors, dtype=self.dtype),
                    np.asarray(host.norms), self.mesh_axes)
            else:
                vec = jnp.asarray(host.vectors, dtype=self.dtype)
                nrm = jnp.asarray(host.norms)
            if self._cos_prenormalized:
                # cos is scale-invariant, so the resident view is normalized
                # ONCE here instead of per query batch inside the fused
                # kernel (an O(N*d) pass on the serving hot path). The norms
                # channel keeps the RAW norms: it is the validity mask
                # (+inf = padding/tombstone) and mutations refresh it.
                rn = jnp.sqrt(jnp.sum(vec.astype(jnp.float32) ** 2,
                                      axis=-1, keepdims=True))
                vec = jnp.where(
                    jnp.isfinite(rn) & (rn > 0),
                    vec / jnp.maximum(rn, 1e-30), 0.0,
                ).astype(self.dtype)
            self._ds = part.PaddedDataset(vec, nrm, host.n_valid, 0)
            if store.has_tier("int8") and self.metric == "l2":
                self._refresh_int8_view()
        self._put_delta_shards()
        return self

    def _row_mult(self, n: int) -> int:
        """Partition-count alignment: rows must split over partitions/shards."""
        mult = part.LANE * self.n_partitions
        if self.mesh is not None:
            total = 1
            for ax in self.mesh_axes:
                total *= self.mesh.shape[ax]
            mult = max(mult, part.LANE * total)
        return mult

    @property
    def store(self):
        """The attached DatasetStore (None before fit)."""
        return self._store

    @property
    def is_fitted(self) -> bool:
        return self._store is not None or self._ds is not None

    @property
    def n(self) -> int:
        self._require_fit()
        if self._store is not None:
            return self._store.n_live
        return self._ds.n_valid

    def _require_fit(self):
        if not self.is_fitted:
            raise RuntimeError("call .fit(dataset) first")

    def _padded_dim(self) -> int:
        return (int(self._ds.vectors.shape[1]) if self._ds is not None
                else self._store.padded_dim)

    def _pad_queries(self, q) -> jax.Array:
        q = jnp.asarray(q, dtype=self.dtype)
        if q.ndim == 1:
            q = q[None, :]
        return part.pad_dim(q, self._padded_dim())

    # ----------------------------------------------------------- mutation
    def upsert(self, vectors) -> np.ndarray:
        """Append rows under live traffic; returns their global ids.

        Rows land in the store's fixed-geometry delta shards, so the next
        query sees them exactly without any recompilation for seen shapes.
        """
        self._require_store_mutable()
        ids = self._store.upsert(vectors)
        self._sync_mutations()
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows by global id; queries exclude them immediately.

        A tombstone is a +inf norm — runtime data, not a shape — so
        compiled executables are untouched ("no reflashing" under churn).
        """
        self._require_store_mutable()
        self._store.delete(ids)
        self._sync_mutations()

    def _require_store_mutable(self):
        self._require_fit()
        if self._store is None:
            raise RuntimeError("engine was fitted without a DatasetStore")

    def _put_norms(self, norms) -> jax.Array:
        """Device view of a norms-like per-row channel: row-sharded over the
        mesh when one is attached (the SAME NamedSharding the fit-time
        shard placed — norms are runtime data, so a mutation re-put never
        touches a compiled executable), default device otherwise."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.device_put(
                np.asarray(norms),
                NamedSharding(self.mesh, PartitionSpec(tuple(self.mesh_axes))),
            )
        return jnp.asarray(norms)

    def _sync_mutations(self) -> None:
        """Re-derive device views after store mutations: norms refresh in
        place (same shapes) and delta shards are re-put; vectors and every
        compiled executable are untouched. Mesh views resync the same way —
        tombstones ride the (re-sharded) norms channel, delta shards stay
        on the default device and merge through the host round-trip in
        :meth:`_merge_delta`.

        A store whose *generation* moved (a compaction folded the delta
        into fresh shards) needs more than a norms refresh — the shard
        count and row layout changed — so the device views are rebuilt
        outright via :meth:`fit_store`. Geometry is preserved across
        generations (rows_per_shard, padded_dim), so every compiled
        executable still applies: the rebuild re-puts data, it compiles
        nothing new."""
        if self._store is None:
            return
        if getattr(self._store, "generation", 0) != self._seen_generation:
            self.fit_store(self._store, resident=self._resident)
            return
        if self._store.mutation_count == self._seen_mutations:
            return
        self._seen_mutations = self._store.mutation_count
        if self._resident and self._ds is not None:
            self._ds = part.PaddedDataset(
                self._ds.vectors, self._put_norms(self._store.resident_norms()),
                self._ds.n_valid, 0,
            )
            if self._int8 is not None:
                # only the norms channel moves on mutation; codes/scales/err
                # were uploaded once at enable_int8()
                self._int8 = self._int8._replace(
                    norms_sq=self._put_norms(self._store.int8_resident_norms())
                )
        self._put_delta_shards()

    def _put_delta_shards(self) -> None:
        if not self._resident:
            # out-of-core queries re-read delta rows from store.iter_shards();
            # a device copy would be pinned memory nothing ever consumes
            self._delta_dev = []
            return
        prev = self._delta_dev
        fresh: list[part.PaddedDataset] = []
        for i, p in enumerate(self._store.delta_shards()):
            if (i < len(prev)
                    and prev[i].n_valid == prev[i].vectors.shape[0] == p.n_valid):
                # a full shard's rows are immutable: reuse its device
                # vectors and re-put only the (tombstone-bearing) norms
                fresh.append(part.PaddedDataset(
                    prev[i].vectors, jnp.asarray(p.norms), p.n_valid, p.base_index
                ))
            else:
                fresh.append(part.PaddedDataset(
                    jnp.asarray(p.vectors, dtype=self.dtype),
                    jnp.asarray(p.norms), p.n_valid, p.base_index,
                ))
        self._delta_dev = fresh

    def _merge_delta(
        self,
        out: TopK,
        queries: jax.Array,
        k: int | None = None,
        metric: str | None = None,
        mask: np.ndarray | None = None,
    ) -> TopK:
        """Fold live delta shards into a main-scan result (exact merge via
        the shared cached partition step — compiled once per delta shape).
        Per-request k/metric ride the step's cache key; a filter mask folds
        onto the norms channel (+inf = excluded, runtime data only)."""
        if not self._delta_dev:
            return out
        k = self.k if k is None else int(k)
        metric = self.metric if metric is None else metric
        step = cached_partition_step(k, metric)
        if self.mesh is not None:
            # a mesh executor's TopK is committed (replicated) across the
            # mesh; the delta arrays live on the default device. Detach the
            # O(m*k) result via host round-trip so the cached step never
            # mixes arrays committed to different devices.
            out = TopK(jnp.asarray(jax.device_get(out.scores)),
                       jnp.asarray(jax.device_get(out.indices)))
        for p in self._delta_dev:
            norms = p.norms
            if mask is not None:
                keep = _keep_rows(mask, p.base_index, p.n_valid,
                                  int(p.vectors.shape[0]))
                norms = jnp.where(jnp.asarray(keep), norms, jnp.inf)
            out = step(out, queries, p.vectors, norms,
                       jnp.int32(p.base_index), jnp.int32(p.n_valid))
        return out

    # ---------------------------------------------------------- int8 tier
    def enable_int8(self) -> "ExactKNN":
        """Materialize the store's int8 tier (the 1 B/element scan tier the
        bandwidth-aware scheduler routes to). Resident engines also build
        the device view; non-resident engines serve the tier by streaming
        the per-shard codes through the fqsd-int8-*streamed executors —
        no device view, and (for disk-backed stores) no f32 reads beyond
        the certified rescore's candidate rows."""
        self._require_fit()
        if self._store is None:
            raise RuntimeError("int8 tier requires a DatasetStore-backed fit")
        if self.metric != "l2":
            raise ValueError("int8 tier supports the l2 metric only")
        self._store.ensure_tier("int8")
        if self._resident:
            self._refresh_int8_view()
        return self

    def _refresh_int8_view(self) -> None:
        i8 = self._store.int8_resident()
        # qnorm_sq was computed at quantize time by the same shared formula
        # (quantized_norm_sq) every QuantizedDataset producer uses, and is
        # persisted with the shard, so engine-path bounds match the raw
        # path bitwise; mutations only ever refresh norms_sq
        if self.mesh is not None:
            # mesh-resident int8: every channel row-shards over the mesh
            # axes (codes 1 B/element per device; the f32 tier stays off
            # the mesh — only candidate rows of it are ever gathered)
            from jax.sharding import NamedSharding, PartitionSpec
            spec = NamedSharding(self.mesh,
                                 PartitionSpec(tuple(self.mesh_axes)))
            self._int8 = QuantizedDataset(
                *(jax.device_put(np.asarray(a), spec)
                  for a in (i8.q, i8.scales, i8.err, i8.norms_sq,
                            i8.qnorm_sq))
            )
            return
        self._int8 = QuantizedDataset(
            jnp.asarray(i8.q), jnp.asarray(i8.scales), jnp.asarray(i8.err),
            jnp.asarray(i8.norms_sq), jnp.asarray(i8.qnorm_sq),
        )

    @property
    def has_int8(self) -> bool:
        """The engine can serve tier="int8": a resident device view exists,
        or (out-of-core) the attached store has the tier materialized for
        the streamed quantized scan."""
        if self._store is not None and not self._resident:
            return self._store.has_tier("int8")
        return self._int8 is not None

    @property
    def last_certificate(self):
        """Per-query exactness certificate of the most recent int8 plan
        (None when the last plan ran a non-quantized executor)."""
        return self._last_ctx.certificate if self._last_ctx else None

    @property
    def last_kernel_stats(self) -> dict | None:
        """Observability from the most recent fused-kernel plan (pruning
        skip rate, resolved tile shapes); None for non-Pallas executors."""
        return self._last_ctx.kernel_stats if self._last_ctx else None

    # ------------------------------------------------------------ planning
    def config(self) -> EngineConfig:
        """The engine's knobs as pure planner input."""
        return EngineConfig(
            k=self.k,
            metric=self.metric,
            backend=self.backend,
            chunk_rows=self.chunk_rows,
            n_partitions=self.n_partitions,
            sharded=self.mesh is not None,
            mesh_axes=self.mesh_axes,
            rescore_factor=self.rescore_factor,
            rescore_pinned=self._rescore_pinned,
            dtype=jnp.dtype(self.dtype).name,
        )

    def dataset_meta(self, tier: str = "f32") -> DatasetMeta:
        """Planner-visible storage facts (a DatasetStoreMeta when a store
        is attached: tier, residency, shard count — ISSUE 2 tentpole)."""
        self._require_fit()
        if self._store is not None:
            return self._store.meta(
                device_resident=self._resident, tier=tier,
                sharded=self.mesh is not None,
            )
        return DatasetMeta(
            padded_rows=int(self._ds.vectors.shape[0]),
            padded_dim=int(self._ds.vectors.shape[1]),
            n_valid=int(self._ds.n_valid),
            sharded=self.mesh is not None,
        )

    def plan_for(self, mode: str, m: int = 1, tier: str = "f32", **kw) -> ExecutionPlan:
        """Plan without executing — what `mode` with an m-row batch would run.

        Pure: calling this any number of times compiles nothing and returns
        equal plans for equal inputs (the scheduler and the benchmarks use
        it to label / choose paths).
        """
        self._require_fit()
        d = self._padded_dim()
        return plan_fn((m, d), self.dataset_meta(tier=tier), self.config(), mode, **kw)

    def _ctx(self, prefetch_depth: int | None = None,
             spec_trigger: float | None = None,
             max_retries: int | None = None,
             allow_partial: bool = False) -> ExecContext:
        return ExecContext(
            mesh=self.mesh, mesh_axes=self.mesh_axes,
            prefetch_depth=(self.prefetch_depth if prefetch_depth is None
                            else prefetch_depth),
            spec_trigger=spec_trigger,
            cos_prenormalized=self._cos_prenormalized,
            max_retries=(self.max_retries if max_retries is None
                         else int(max_retries)),
            retry_backoff_s=self.retry_backoff_s,
            allow_partial=bool(allow_partial),
        )

    def _run(self, p: ExecutionPlan, queries: jax.Array, dataset, **ctx_kw) -> TopK:
        self._plans.append(p)
        ctx = self._ctx(**ctx_kw)
        self._last_ctx = ctx
        return execute(p, queries, dataset, ctx)

    @property
    def plans(self) -> list[ExecutionPlan]:
        """Every plan executed, in order (observability / tests)."""
        return list(self._plans)

    # ------------------------------------------------------------ request API
    @property
    def n_ids(self) -> int:
        """Size of the external row-id space (every id ever allocated,
        including tombstoned and compacted-away ids — ids are never
        reused). ``SearchRequest.filter_mask`` must have exactly this
        length."""
        self._require_fit()
        if self._store is not None:
            n = getattr(self._store, "n_ids", None)
            if n is not None:
                return int(n)
            return self._store.n_main + self._store.n_delta
        return int(self._ds.n_valid)

    def _masked_resident(self, mask: np.ndarray | None) -> part.PaddedDataset:
        """Resident f32 view with a per-request filter mask folded onto the
        norms channel (+inf = excluded — runtime data, so filtering never
        changes compiled shapes)."""
        ds = self._ds
        if mask is None:
            return ds
        # ds.n_valid IS the fit-time main-row count of this resident view
        # (not re-read from the store: a racing compaction must not skew
        # the slice against the arrays already on device)
        keep = _keep_rows(mask, 0, ds.n_valid, int(ds.vectors.shape[0]))
        norms = jnp.where(self._put_like(keep, ds.norms), ds.norms, jnp.inf)
        return part.PaddedDataset(ds.vectors, norms, ds.n_valid, ds.base_index)

    def _put_like(self, host_arr: np.ndarray, ref: jax.Array) -> jax.Array:
        """Ship a host per-row channel next to `ref` (same NamedSharding on
        a mesh view) so masking a sharded channel never gathers it."""
        if self.mesh is not None:
            return jax.device_put(np.asarray(host_arr), ref.sharding)
        return jnp.asarray(host_arr)

    def _masked_int8(self, mask: np.ndarray | None) -> QuantizedDataset:
        """Int8 view under the same per-request mask (norms_sq is the int8
        executors' validity channel, exactly like f32 norms)."""
        q8 = self._int8
        if mask is None:
            return q8
        n_main = (self._ds.n_valid if self._ds is not None
                  else self._store.n_main)
        keep = _keep_rows(mask, 0, n_main, int(q8.norms_sq.shape[0]))
        return q8._replace(
            norms_sq=jnp.where(self._put_like(keep, q8.norms_sq),
                               q8.norms_sq, jnp.inf)
        )

    def search(self, request: SearchRequest) -> SearchResult:
        """Serve one :class:`SearchRequest` — the single entry point.

        Normalizes every per-request option (k, metric, tier, mode, filter
        mask, deadline) and routes through ``planner.plan`` so the option
        set rides ``ExecutionPlan.cache_key()``: a request with k ≠ the
        engine's configured k returns results bit-identical to a fresh
        engine built with that k, and hits exactly the executables such an
        engine would have compiled (the autotune key already carries k).

        tier="auto" serves the exact f32 base tier; the serving layer's
        bandwidth-aware policy may upgrade auto requests to int8 per batch.
        mode_hint="auto" takes the FD-SQ latency plan for micro-batches
        (<= AUTO_FDSQ_MAX_BATCH rows) and the FQ-SD throughput plan beyond.
        """
        if not isinstance(request, SearchRequest):
            raise TypeError(
                f"search() takes a SearchRequest, got {type(request).__name__}"
            )
        self._require_fit()
        k = self.k if request.k is None else int(request.k)
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        metric = self.metric if request.metric is None else request.metric
        validate_metric(metric)
        if self._cos_prenormalized and metric != "cos":
            raise ValueError(
                "this engine L2-normalized its resident rows at fit time "
                f"(cos metric, pallas backend); per-request metric={metric!r} "
                "would score normalized rows — fit a separate engine"
            )
        tier = "f32" if request.tier == "auto" else request.tier
        if tier == "int8":
            if request.mode_hint == "fdsq":
                raise ValueError(
                    "tier='int8' is a throughput (FQ-SD) tier and cannot "
                    "serve an explicit mode_hint='fdsq' request"
                )
            if not self.has_int8:
                raise RuntimeError("int8 tier not enabled; call enable_int8() first")
            if metric != "l2":
                raise ValueError("int8 tier supports the l2 metric only")
        self._sync_mutations()
        # pin the generation this search scans: a concurrent compaction may
        # swap the store's live generation mid-flight, and the pin keeps the
        # pinned shards (and the id tables that interpret their positions)
        # alive until the search completes. The loop re-syncs until the
        # pinned generation matches the device views — they must agree, or
        # resident arrays and store positions would describe different rows.
        view = None
        if self._store is not None and hasattr(self._store, "snapshot"):
            view = self._store.snapshot()
            while view.generation != self._seen_generation:
                view.release()
                self._sync_mutations()
                view = self._store.snapshot()
        try:
            return self._search_pinned(request, k, metric, tier, view)
        finally:
            if view is not None:
                view.release()

    def _search_pinned(self, request: SearchRequest, k: int, metric: str,
                       tier: str, view) -> SearchResult:
        """The body of :meth:`search`, run with `view` pinning the store
        generation the engine's device views mirror (None when no store /
        a store without generations is attached). Filter masks arrive in
        EXTERNAL id space and are translated to the generation's positional
        layout here; result indices are translated back at the end — in
        between, everything is positional."""
        qv = self._pad_queries(request.queries)
        m = int(qv.shape[0])
        mode = request.mode_hint
        if tier == "int8":
            mode = "fqsd"
        elif mode == "auto":
            mode = "fdsq" if m <= AUTO_FDSQ_MAX_BATCH else "fqsd"
        mask = request.filter_mask
        if mask is not None:
            mask = np.asarray(mask, dtype=bool).reshape(-1)
            if mask.shape[0] != self.n_ids:
                raise ValueError(
                    "filter_mask must cover the engine's global id space "
                    f"({self.n_ids} rows), got {mask.shape[0]}"
                )
            if view is not None:
                mask = view.positional_mask(mask)
        max_retries = (self.max_retries if request.max_retries is None
                       else int(request.max_retries))
        allow_partial = bool(request.allow_partial)
        t0 = time.perf_counter()
        # every read below goes through the pinned view when one exists, so
        # a mid-search generation swap cannot mix shards of two epochs
        src_store = view if view is not None else self._store
        meta = (view.meta(device_resident=self._resident, tier=tier,
                          sharded=self.mesh is not None)
                if view is not None else self.dataset_meta(tier=tier))
        if not self._resident:
            # tier="int8" survives planning here: the out-of-core scan
            # streams 1 B/element codes and rescores candidate rows only
            p = plan_fn(
                qv.shape, meta, self.config(),
                "fqsd-streamed",
                stream_rows=src_store.rows_per_shard, k=k, metric=metric,
            )
            source = (src_store if mask is None
                      else _MaskedShardSource(src_store, mask))
            # pipeline-knob precedence: request pin > engine pin > tuned
            # plan > engine default (the executor resolves a None trigger
            # against plan.spec_trigger, then DEFAULT_SPEC_TRIGGER)
            if request.prefetch_depth is not None:
                prefetch = int(request.prefetch_depth)
            elif self._prefetch_pinned or p.prefetch_depth <= 0:
                prefetch = self.prefetch_depth
            else:
                prefetch = int(p.prefetch_depth)
            trigger = (request.spec_trigger
                       if request.spec_trigger is not None
                       else self.spec_trigger)
            out = self._run(p, qv, source, prefetch_depth=prefetch,
                            spec_trigger=trigger, max_retries=max_retries,
                            allow_partial=allow_partial)
            # streamed scans fold delta shards (mask applied) in-pass
        else:
            p = plan_fn(
                (m, self._padded_dim()), meta,
                self.config(), mode, k=k, metric=metric,
            )
            if p.executor == "fdsq-sharded-int8":
                # mesh-resident int8: the sharded quantized view plus the
                # backing store for the candidate-only f32 rescore (masked
                # view when the request filters — gather/delta/fallback all
                # see the same exclusions)
                src = (src_store if mask is None
                       else _MaskedShardSource(src_store, mask))
                dataset = MeshTiered(self._masked_int8(mask), src)
            elif p.tier == "int8":
                dataset = TieredResident(self._masked_resident(mask),
                                         self._masked_int8(mask))
            else:
                dataset = self._masked_resident(mask)
            out = self._run(p, qv, dataset, max_retries=max_retries,
                            allow_partial=allow_partial)
            if not self._last_ctx.delta_folded:
                out = self._merge_delta(out, qv, k=k, metric=metric, mask=mask)
        if view is not None and not view.identity:
            # positions within a compacted generation are internal — hand
            # the caller back the stable external ids (a pure relabeling of
            # the indices channel; scores and ordering are untouched)
            idx = np.asarray(jax.device_get(out.indices))
            out = TopK(out.scores, jnp.asarray(view.external_ids(idx)))
        dispatch_ms = (time.perf_counter() - t0) * 1e3
        ctx = self._last_ctx
        cert = ctx.certificate if (ctx is not None and p.tier == "int8") else None
        stats = {
            "k": k, "metric": metric, "m": m, "batched": m,
            # executors whose traffic the plan geometry cannot predict
            # (streamed int8: codes + side channels + candidate-row reads)
            # report honest bytes on the ctx; plans predict the rest
            "bytes_scanned": (
                ctx.bytes_scanned
                if ctx is not None and ctx.bytes_scanned is not None
                else p.padded_rows * p.padded_dim
                * (1 if p.tier == "int8" else 4)
            ),
            "dispatch_ms": dispatch_ms,
        }
        if ctx is not None and ctx.stream_stats is not None:
            stats["transfers"] = ctx.stream_stats.get("transfers", 0)
            stats["restarts"] = ctx.stream_stats.get("restarts", 0)
        if ctx is not None and ctx.device_bytes is not None:
            # mesh executors: the scan-bytes split per device (the total —
            # incl. gather/delta/fallback traffic — is bytes_scanned above)
            stats["bytes_per_device"] = list(ctx.device_bytes)
        if ctx is not None and ctx.phase_ms is not None:
            # the streamed int8 wall-time split (scan / gather / rescore)
            stats.update(ctx.phase_ms)
        if ctx is not None and ctx.speculation is not None:
            stats["speculation"] = dict(ctx.speculation)
        # health is ALWAYS present: a fault-free search reports an all-clear
        # block, so serving aggregation / dashboards never branch on its
        # absence. Shard lists are dedup'd + sorted (a shard can degrade on
        # multiple reads of one scan).
        h = ctx.health if (ctx is not None and ctx.health is not None) else {}
        health = {
            "retries": int(h.get("retries", 0)),
            "failed_shards": sorted(set(h.get("failed_shards", ()))),
            "degraded": sorted(set(h.get("degraded", ()))),
            "slow_shards": sorted(set(h.get("slow_shards", ()))),
            "shed": False,
        }
        stats["health"] = health
        # partial is loud: only an allow_partial=True request can ever see
        # it, and it means failed_shards' rows are missing from topk.
        stats["partial"] = bool(health["failed_shards"])
        if request.deadline_ms is not None:
            stats["deadline_ms"] = request.deadline_ms
        return SearchResult(
            topk=out, plan=p, tier=p.tier,
            certified=True if cert is None else cert,
            kernel_stats=ctx.kernel_stats if ctx is not None else None,
            stats=stats, rid=request.rid,
        )

    # ------------------------------------------- deprecated query_* shims
    def query(self, q) -> TopK:
        """Deprecated low-latency path; delegates to :meth:`search`."""
        _deprecated_shim("query(q)", "queries=q, mode_hint='fdsq'")
        return self.search(SearchRequest(queries=q, mode_hint="fdsq")).topk

    def query_stream(self, queries_iter: Iterable) -> Iterable[TopK]:
        """Deprecated streamed-queries path; delegates to :meth:`search`."""
        _deprecated_shim("query_stream(qs)", "queries=q, mode_hint='fdsq'")
        for q in queries_iter:
            out = self.search(SearchRequest(queries=q, mode_hint="fdsq")).topk
            yield TopK(out.scores[0], out.indices[0])

    def query_batch(self, queries) -> TopK:
        """Deprecated throughput path; delegates to :meth:`search`."""
        _deprecated_shim("query_batch(Q)", "queries=Q, mode_hint='fqsd'")
        return self.search(
            SearchRequest(queries=queries, mode_hint="fqsd")
        ).topk

    def query_batch_int8(self, queries) -> TopK:
        """Deprecated int8-tier path; delegates to :meth:`search`."""
        _deprecated_shim("query_batch_int8(Q)", "queries=Q, tier='int8'")
        return self.search(
            SearchRequest(queries=queries, tier="int8", mode_hint="fqsd")
        ).topk

    def search_streamed(
        self,
        queries,
        host_vectors: np.ndarray,
        rows_per_partition: int = 65536,
        prefetch_depth: int = 2,
    ) -> TopK:
        """FQ-SD over a host dataset too large for device memory (fig. 1).

        Deprecated legacy iterator path: prefer attaching a (possibly
        non-resident) DatasetStore and calling :meth:`search` — e.g.
        ``fit_store(DatasetStore.from_array(x, rows_per_shard=...),
        resident=False)`` then ``search(SearchRequest(queries=Q))``.
        """
        warnings.warn(
            "ExactKNN.search_streamed() is deprecated; attach a "
            "non-resident DatasetStore (fit_store(..., resident=False)) "
            "and call search(SearchRequest(queries=Q)) (see docs/api.md)",
            DeprecationWarning, stacklevel=2,
        )
        q = jnp.asarray(queries, dtype=self.dtype)
        if q.ndim == 1:
            q = q[None, :]
        d_pad = part.round_up(host_vectors.shape[1], part.LANE)
        q = part.pad_dim(q, d_pad)
        rows = part.round_up(rows_per_partition, part.LANE)
        meta = DatasetMeta(
            padded_rows=int(host_vectors.shape[0]),
            padded_dim=d_pad,
            n_valid=int(host_vectors.shape[0]),
            resident=False,
        )
        p = plan_fn(
            q.shape, meta, self.config(), "fqsd-streamed", stream_rows=rows
        )
        parts = part.iter_partitions(host_vectors, rows)
        return self._run(p, q, parts, prefetch_depth=prefetch_depth)


__all__ = ["ExactKNN", "EnginePlan", "ExecutionPlan",
           "SearchRequest", "SearchResult"]
