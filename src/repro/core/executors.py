"""Executor registry — uniform "run the plan" layer of the engine.

Each executor is a callable ``execute(plan, queries, dataset, ctx) -> TopK``
registered under the name the planner selects (``repro.core.planner``).
Executors wrap the existing entry points (``fdsq.py`` / ``fqsd.py`` /
``sharded.py`` / ``kernels.knn``) — they add no numerics of their own.

The module also owns the **executable cache**, the TPU analogue of the
paper's fixed FPGA bitstream: every executor resolves its compiled
executable through :func:`_cached`, keyed by ``plan.cache_key()`` plus the
concrete array shapes. Switching FD-SQ <-> FQ-SD therefore never recompiles
for shapes already seen ("no reflashing", section 3.2) — and because the
cache is explicit, that invariant is directly testable via
:func:`cache_info` (see tests/test_planner.py) instead of being an
accident of jit internals.

The cache is a bounded LRU (:func:`set_executable_cache_limit`): autotune
sweeps and long-lived multi-tenant servers plan many distinct keys, and an
unbounded map would pin every executable ever compiled. Evictions are
counted in :func:`cache_info` so tests (and dashboards) can tell a genuine
recompile from an eviction-induced one.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import partition as part
from repro.core import sharded as sh
from repro.core.fdsq import fdsq_search
from repro.core.fqsd import (
    fqsd_scan,
    fqsd_streamed,
    make_direct_partition_step,
    make_partition_step,
)
from repro.core.planner import ExecutionPlan
from repro.core.quantized import (
    QuantizedDataset,
    knn_quantized,
    make_int8_bound_step,
)
from repro.core.streaming import (
    DoubleBufferedStream,
    ResilientShardSource,
    SpeculativeGather,
    _fresh_health,
    device_put_partition,
    make_ring_put,
)
from repro.core.topk import TopK, sort_pairs

#: Default speculation trigger for the streamed int8 executors: start the
#: background candidate gather once this fraction of shards has merged.
#: 1.0 disables speculation (gather strictly after the scan, the pre-ISSUE-6
#: schedule); tuned per device by repro.tuning.autotune_pipeline.
DEFAULT_SPEC_TRIGGER = 0.5


@dataclasses.dataclass
class ExecContext:
    """Runtime state a plan cannot carry (plans are pure data): the mesh
    handle, axis names, and host-streaming knobs. Executors may also write
    run observability back here (the int8 exactness certificate)."""

    mesh: jax.sharding.Mesh | None = None
    mesh_axes: Sequence[str] = ("data", "model")
    prefetch_depth: int = 2
    certificate: jax.Array | None = None  # set by int8 executors: (m,) bool
    #: set by the fused Pallas executors: {"prune_skip_rate": 0-d array,
    #: "blocks": (bm, bn, bd)}. The skip rate stays a device scalar so
    #: publishing stats never forces a host sync; float() it lazily.
    kernel_stats: dict | None = None
    #: the resident dataset rows were L2-normalized at fit time (cos metric
    #: via the fused kernel: the kernel then skips its own dataset pass)
    cos_prenormalized: bool = False
    #: set by the streamed executors: {"transfers": n, "restarts": n} from
    #: the double buffer (serving observability; scheduler stats aggregate)
    stream_stats: dict | None = None
    #: set by executors whose traffic the plan geometry cannot predict
    #: (streamed int8: codes + per-row channels + candidate-row rescore
    #: reads); None = the engine derives bytes from the plan
    bytes_scanned: int | None = None
    #: speculation trigger override for the streamed int8 executors; None
    #: defers to the plan's tuned value, then DEFAULT_SPEC_TRIGGER
    spec_trigger: float | None = None
    #: set by the streamed int8 executors: {"scan_ms", "gather_ms",
    #: "rescore_ms"} — the wall-time split of the pipelined search
    phase_ms: dict | None = None
    #: set by the streamed int8 executors: {"trigger", "rows_speculated",
    #: "rows_topped_up", "rows_wasted"} — wasted speculative fetches are
    #: also charged to bytes_scanned (honest traffic accounting)
    speculation: dict | None = None
    #: set by the mesh int8 executors: scan bytes each device moved (list of
    #: len = device count). The total still lands on bytes_scanned; this is
    #: the per-device split (gather/delta/fallback traffic is host-side and
    #: charged to the total only).
    device_bytes: list | None = None
    #: set by executors that merge the store's delta shards themselves (the
    #: int8 rescore tail does); the engine then skips its own delta merge so
    #: upserted rows are never scored twice
    delta_folded: bool = False
    #: bounded-retry budget for host-side shard reads, candidate gathers,
    #: and device_put transfers (exponential backoff from retry_backoff_s)
    max_retries: int = 2
    retry_backoff_s: float = 0.05
    #: the request opted into partial results: a shard unrecoverable on
    #: every tier is skipped and listed in health["failed_shards"] instead
    #: of failing the search (the engine flags the result "partial")
    allow_partial: bool = False
    #: resilience accounting for this run ({"retries", "degraded",
    #: "failed_shards", "slow_shards"}); created lazily by the streamed
    #: executors and surfaced by the engine as stats["health"]
    health: dict | None = None


class TieredResident(NamedTuple):
    """Resident dataset carrying both tiers: the exact f32 base and the
    1 B/element int8 scan tier (what the fqsd-int8 executor consumes)."""

    f32: part.PaddedDataset
    quant: QuantizedDataset


class MeshTiered(NamedTuple):
    """Mesh-resident int8 tier (what fdsq-sharded-int8 consumes): the
    quantized arrays row-sharded over the mesh axes (NamedSharding), plus
    the backing DatasetStore for the candidate-only f32 rescore
    (``gather_rows``) and the exact streamed fallback. The f32 tier never
    lives on the mesh — only candidate rows of it are ever read."""

    quant: QuantizedDataset
    store: object


Executor = Callable[[ExecutionPlan, jax.Array, object, ExecContext], TopK]

_REGISTRY: dict[str, Executor] = {}
_EXECUTABLE_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
#: LRU bound on compiled executables (None = unbounded). Generous enough
#: that serving workloads never evict (they cycle O(log max_batch) plans);
#: tight enough that autotune sweeps cannot grow the cache without limit.
_CACHE_MAX_ENTRIES: int | None = 256


# ----------------------------------------------------------------- registry
def register_executor(name: str):
    """Class-of-2 decorator: ``@register_executor("fdsq-xla")``."""

    def deco(fn: Executor) -> Executor:
        if name in _REGISTRY:
            raise ValueError(f"executor {name!r} already registered")
        fn.executor_name = name
        _REGISTRY[name] = fn
        return fn

    return deco


def get_executor(name: str) -> Executor:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown executor {name!r}; registered: {list_executors()}"
        ) from None


def list_executors() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def execute(
    plan: ExecutionPlan,
    queries: jax.Array,
    dataset,
    ctx: ExecContext | None = None,
) -> TopK:
    """Dispatch `plan` to its registered executor."""
    return get_executor(plan.executor)(plan, queries, dataset, ctx or ExecContext())


# ------------------------------------------------------- executable cache
def _cached(key: tuple, build: Callable[[], Callable]) -> Callable:
    try:
        fn = _EXECUTABLE_CACHE[key]
        _EXECUTABLE_CACHE.move_to_end(key)  # LRU: reads refresh recency
        _CACHE_STATS["hits"] += 1
        return fn
    except KeyError:
        fn = _EXECUTABLE_CACHE[key] = build()
        _CACHE_STATS["misses"] += 1
        _evict_over_limit()
        return fn


def _evict_over_limit() -> None:
    if _CACHE_MAX_ENTRIES is None:
        return
    while len(_EXECUTABLE_CACHE) > _CACHE_MAX_ENTRIES:
        _EXECUTABLE_CACHE.popitem(last=False)  # least recently used
        _CACHE_STATS["evictions"] += 1


def set_executable_cache_limit(max_entries: int | None) -> None:
    """Bound the executable cache (None = unbounded). Shrinking evicts the
    least-recently-used executables immediately (counted in cache_info)."""
    global _CACHE_MAX_ENTRIES
    if max_entries is not None and max_entries < 1:
        raise ValueError(f"max_entries must be >= 1 or None, got {max_entries}")
    _CACHE_MAX_ENTRIES = max_entries
    _evict_over_limit()


def cache_info() -> dict:
    """{"hits", "misses", "evictions", "size", "max_entries"} — misses ==
    number of compiles triggered; evictions == executables dropped by the
    LRU bound (a later re-plan of an evicted key recompiles = new miss)."""
    return {
        **_CACHE_STATS,
        "size": len(_EXECUTABLE_CACHE),
        "max_entries": _CACHE_MAX_ENTRIES,
    }


def compiled_texts(executor: str) -> list[str]:
    """HLO text of every cached compiled executable of `executor` (built
    by ``.lower().compile()``). A fused executor compiled for a TPU shows
    its Mosaic kernel as a ``tpu_custom_call``; in interpret mode the
    kernel is plain HLO and no such call appears."""
    return [fn.as_text() for key, fn in _EXECUTABLE_CACHE.items()
            if isinstance(key[0], tuple) and key[0][:1] == (executor,)
            and hasattr(fn, "as_text")]


def clear_executable_cache() -> None:
    _EXECUTABLE_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0
    _CACHE_STATS["evictions"] = 0


def _arr_key(a: jax.Array) -> tuple:
    return (tuple(a.shape), str(a.dtype))


def cached_partition_step(k: int, metric: str) -> Callable:
    """The shared streamed-scan step (one partition into the queues).

    One cache entry serves the host-streamed executors AND the engine's
    delta-shard merge: every consumer of (k, metric) reuses the same step
    wrapper, whose jit resolves each padded shard shape to one executable.
    """
    return _cached(("partition-step", k, metric),
                   lambda: make_partition_step(k, metric))


# ------------------------------------------------------------- executors
@register_executor("fdsq-xla")
def _fdsq_xla(plan, queries, dataset: part.PaddedDataset, ctx) -> TopK:
    """Partition-parallel fan-out + tree merge (latency path, fig. 2)."""
    key = (plan.cache_key(), _arr_key(queries), _arr_key(dataset.vectors))

    def build():
        return fdsq_search.lower(
            queries, dataset.vectors, dataset.norms,
            plan.k, plan.metric, plan.n_partitions,
        ).compile()

    return _cached(key, build)(queries, dataset.vectors, dataset.norms)


@register_executor("fqsd-xla")
def _fqsd_xla(plan, queries, dataset: part.PaddedDataset, ctx) -> TopK:
    """Chunked streaming queue scan over resident data (throughput, fig. 1)."""
    key = (plan.cache_key(), _arr_key(queries), _arr_key(dataset.vectors))

    def build():
        return fqsd_scan.lower(
            queries, dataset.vectors, dataset.norms,
            plan.k, plan.metric, plan.chunk_rows,
        ).compile()

    return _cached(key, build)(queries, dataset.vectors, dataset.norms)


def _plan_blocks(plan) -> tuple[int, int, int]:
    """Resolve a plan's (possibly autotuned) kernel tile shapes; 0 = cold
    tuning cache = the kernel defaults."""
    from repro.kernels.knn.ops import DEFAULT_BLOCKS

    return (plan.block_m or DEFAULT_BLOCKS[0],
            plan.block_n or DEFAULT_BLOCKS[1],
            plan.block_d or DEFAULT_BLOCKS[2])




@register_executor("fdsq-pallas")
def _fdsq_pallas(plan, queries, dataset: part.PaddedDataset, ctx) -> TopK:
    """Fused distance+queue kernel; one executable serves both logical modes
    (interpret mode off-TPU, MXU/VMEM pipeline on hardware). Tile shapes
    come from the plan (autotuned) and the measured threshold-pruning skip
    rate is published on ctx.kernel_stats."""
    from repro.kernels.knn import ops as knn_ops

    bm, bn, bd = _plan_blocks(plan)
    pre = bool(ctx.cos_prenormalized) and plan.metric == "cos"
    key = (plan.cache_key(), _arr_key(queries), _arr_key(dataset.vectors), pre)

    def build():
        return knn_ops.knn.lower(
            queries, dataset.vectors, plan.k, plan.metric, dataset.norms,
            block_m=bm, block_n=bn, block_d=bd, return_stats=True,
            x_prenormalized=pre,
        ).compile()

    out, skip_rate = _cached(key, build)(queries, dataset.vectors, dataset.norms)
    ctx.kernel_stats = {
        "prune_skip_rate": skip_rate,
        # resolved through ops.py so stats report the tiles that ACTUALLY ran
        "blocks": knn_ops.resolved_blocks(plan.k, plan.padded_dim, bm, bn, bd),
    }
    return out


@register_executor("fqsd-streamed")
def _fqsd_streamed(plan, queries, dataset: Iterable[part.PaddedDataset], ctx) -> TopK:
    """Host-streamed FQ-SD through the double buffer. The per-partition step
    is the cached executable (all partitions share one padded shape).

    Keyed by (k, metric) only — the step's jit resolves shapes itself, so
    datasets of different total size reuse one wrapper (compiles once)."""
    step = cached_partition_step(plan.k, plan.metric)
    ctx.stream_stats = {}
    return fqsd_streamed(
        queries, dataset, plan.k, plan.metric,
        prefetch_depth=ctx.prefetch_depth, step_fn=step,
        stream_stats=ctx.stream_stats,
        put_retries=ctx.max_retries, retry_backoff_s=ctx.retry_backoff_s,
        health=_ctx_health(ctx),
    )


@register_executor("fqsd-mmap-streamed")
def _fqsd_mmap_streamed(plan, queries, dataset, ctx) -> TopK:
    """Manifest-driven FQ-SD over a DatasetStore too large for the device
    budget (out-of-core). `dataset` is the store itself (duck-typed:
    `.iter_shards()` yields equal-geometry PaddedDataset host shards,
    memmap-backed when the store lives on disk).

    Each shard's bytes leave the disk inside the double buffer's
    device_put, overlapped with compute on the previous shard (paper
    section 3.3); delta shards and tombstones ride along, so results stay
    exact under live mutation. Shares the cached partition step with
    fqsd-streamed — same (k, metric) never compiles twice across paths.
    """
    step = cached_partition_step(plan.k, plan.metric)
    ctx.stream_stats = {}
    source = (_resilient_source(dataset, "f32", ctx)
              if hasattr(dataset, "read_shard") else dataset.iter_shards())
    return fqsd_streamed(
        queries, source, plan.k, plan.metric,
        prefetch_depth=ctx.prefetch_depth, step_fn=step,
        stream_stats=ctx.stream_stats,
        put_retries=ctx.max_retries, retry_backoff_s=ctx.retry_backoff_s,
        health=_ctx_health(ctx),
    )


@register_executor("fqsd-int8")
def _fqsd_int8(plan, queries, dataset: TieredResident, ctx) -> TopK:
    """Quantized FQ-SD: int8 first pass (4x less memory traffic than f32 —
    the FQ-SD bottleneck, paper section 5) + exact f32 rescore.

    The per-query certificate proves the rescore budget covered every
    possible true neighbor (repro.core.quantized); it is published on
    `ctx.certificate`. Rows the certificate cannot cover are recomputed
    through a cached exact f32 scan of the SAME shapes, so the returned
    top-k is exact for every row regardless of certification.
    """
    q8 = dataset.quant
    key = (plan.cache_key(), _arr_key(queries), _arr_key(q8.q))

    def build():
        return knn_quantized.lower(
            queries, q8, dataset.f32.vectors, plan.k, plan.rescore_factor,
        ).compile()

    out, cert = _cached(key, build)(queries, q8, dataset.f32.vectors)
    ctx.certificate = cert
    if not bool(jax.device_get(cert).all()):
        fkey = ("int8-fallback", plan.cache_key(),
                _arr_key(queries), _arr_key(dataset.f32.vectors))

        def build_fallback():
            return fqsd_scan.lower(
                queries, dataset.f32.vectors, dataset.f32.norms,
                plan.k, plan.metric, plan.chunk_rows,
            ).compile()

        exact = _cached(fkey, build_fallback)(
            queries, dataset.f32.vectors, dataset.f32.norms
        )
        keep = cert[:, None]
        out = TopK(jnp.where(keep, out.scores, exact.scores),
                   jnp.where(keep, out.indices, exact.indices))
    return out


@register_executor("fqsd-int8-pallas")
def _fqsd_int8_pallas(plan, queries, dataset: TieredResident, ctx) -> TopK:
    """Fused quantized FQ-SD: the int8 Pallas scan streams the dataset at
    1 B/element, keeps the widened candidate queue in VMEM, and the exact
    rescore reads ONLY the candidate rows of the f32 tier — distances and
    bounds never touch HBM (paper sections 3.2 + 5 combined).

    Exactness mirrors fqsd-int8: the per-query certificate (published on
    ctx.certificate) proves the on-chip candidate set covered every
    possible true neighbor; uncertified rows are recomputed by a cached
    direct-form exact scan of the SAME padded shapes, so the returned
    top-k is exact for every row. The kernel's threshold-pruning skip rate
    and tile shapes land on ctx.kernel_stats."""
    from repro.kernels.knn import ops as knn_ops

    q8 = dataset.quant
    bm, bn, bd = _plan_blocks(plan)
    key = (plan.cache_key(), _arr_key(queries), _arr_key(q8.q))

    def build():
        return knn_ops.knn_int8.lower(
            queries, q8, dataset.f32.vectors, plan.k, plan.rescore_factor,
            block_m=bm, block_n=bn, block_d=bd, return_stats=True,
        ).compile()

    out, cert, skip_rate = _cached(key, build)(queries, q8, dataset.f32.vectors)
    ctx.certificate = cert
    ctx.kernel_stats = {
        "prune_skip_rate": skip_rate,
        "blocks": knn_ops.resolved_blocks(plan.k, plan.padded_dim, bm, bn, bd,
                                          rescore_factor=plan.rescore_factor),
    }
    if not bool(jax.device_get(cert).all()):
        fkey = ("int8-pallas-fallback", plan.cache_key(),
                _arr_key(queries), _arr_key(dataset.f32.vectors))

        def build_fallback():
            return knn_ops.knn_exact_direct.lower(
                queries, dataset.f32.vectors, dataset.f32.norms,
                plan.k, plan.chunk_rows,
            ).compile()

        exact = _cached(fkey, build_fallback)(
            queries, dataset.f32.vectors, dataset.f32.norms
        )
        keep = cert[:, None]
        out = TopK(jnp.where(keep, out.scores, exact.scores),
                   jnp.where(keep, out.indices, exact.indices))
    return out


def _ctx_health(ctx: ExecContext) -> dict:
    if ctx.health is None:
        ctx.health = _fresh_health()
    return ctx.health


def _with_retries(fn: Callable, ctx: ExecContext):
    """Bounded retry with exponential backoff for host-side store ops
    (candidate gathers, delta device_puts). Every failed attempt counts
    into ``ctx.health["retries"]``; the last failure propagates."""
    import time

    delay = ctx.retry_backoff_s
    for attempt in range(ctx.max_retries + 1):
        try:
            return fn()
        except Exception:
            _ctx_health(ctx)["retries"] += 1
            if attempt == ctx.max_retries:
                raise
            if delay > 0:
                time.sleep(delay)
                delay *= 2


def _resilient_source(store, tier: str, ctx: ExecContext):
    """The shard source of a streamed scan: self-healing when the dataset
    exposes per-shard reads (``read_shard`` — DatasetStore and the
    engine's masked view), the store's plain restartable source otherwise
    (legacy duck-typed datasets keep their exact old behavior)."""
    if hasattr(store, "read_shard"):
        return ResilientShardSource(
            store, tier, max_retries=ctx.max_retries,
            backoff_s=ctx.retry_backoff_s,
            allow_partial=ctx.allow_partial, health=_ctx_health(ctx),
        )
    return store.shard_source(tier)


def _make_stream_rescore(k: int) -> Callable:
    """Exact candidate rescore for the streamed int8 executors: direct-form
    (q - x)^2 over the gathered candidate rows, lexicographic (value, index)
    sort — the same formula and tie order as the streamed direct oracle, so
    certified rows are bitwise equal to it."""

    @jax.jit
    def rescore(queries, cand_vecs, cand_idx):
        q32 = queries.astype(jnp.float32)
        diff = q32[:, None, :] - cand_vecs.astype(jnp.float32)
        exact = jnp.sum(diff * diff, axis=-1)
        exact = jnp.where(cand_idx >= 0, exact, jnp.inf)
        s, i = sort_pairs(exact, cand_idx)
        return s[:, :k], i[:, :k]

    return rescore


def _rescore_budget(plan) -> int:
    """The resolved candidate budget r of an int8 plan: rescore_factor * k,
    clamped to the dataset (and >= 1 so the widened queue always exists)."""
    return max(1, min(int(plan.padded_rows), int(plan.rescore_factor) * plan.k))


def _rescore_certify(plan, queries, store, ctx, lb, li, scan_bytes,
                     spec=None, t_start=None, trigger=1.0) -> TopK:
    """Shared epilogue of every certified-int8 executor that scans through a
    DatasetStore (single-device streamed AND the mesh paths): given the
    final widened (m, r+1) lower-bound queue, gather the candidate f32 rows
    (reusing a speculative gather when one ran), rescore exactly, merge the
    live delta shards, certify, and fall back to the streamed f32 oracle
    for uncertified queries.

    ``lb``/``li`` may be committed anywhere (a mesh-replicated shard_map
    output or the default-device streamed queue): the epilogue syncs them
    to host and runs on the default device, so mesh-committed scan outputs
    never mix with default-device delta/rescore arrays. Phases 2+3 of the
    :func:`_int8_streamed` docstring, verbatim — one body is what makes
    every int8 executor bit-identical to the streamed f32 oracle.
    """
    import time

    if t_start is None:
        t_start = time.perf_counter()
    m = int(queries.shape[0])
    r = _rescore_budget(plan)
    direct_step = _cached(("direct-step", plan.k),
                          lambda: make_direct_partition_step(plan.k))
    rescore = _cached(("int8-stream-rescore", plan.k),
                      lambda: _make_stream_rescore(plan.k))
    if ctx.stream_stats is None:
        ctx.stream_stats = {"transfers": 0, "restarts": 0}

    # pull ONLY the candidate indices to host, dedup across queries
    cand_idx = np.asarray(li[:, :r])
    # best lower bound OUTSIDE the candidate set; host round-trip detaches
    # it from whatever device/mesh produced the queue
    lb_r1 = jnp.asarray(np.asarray(lb[:, r]))
    t_scan = time.perf_counter()
    uniq, inv = np.unique(cand_idx, return_inverse=True)
    rows_speculated = rows_topped = rows_wasted = 0
    spec_failed = 0
    spec_res = spec.result() if spec is not None else None
    if spec is not None and spec_res is None:
        # the background gather died (flaky disk, injected fault): the
        # speculation was only ever a read reschedule, so degrade to the
        # synchronous gather of the FINAL ids — bit-identical, just slower
        spec_failed = 1
    if spec_res is not None:
        spec_ids, spec_rows = spec_res
        # diff the final queue against the snapshot: reuse hits by id,
        # top up only the ids the late shards added
        pos = np.searchsorted(spec_ids, uniq)
        pos_c = np.minimum(pos, max(0, spec_ids.size - 1))
        hit = (spec_ids[pos_c] == uniq) if spec_ids.size else \
            np.zeros(uniq.shape, bool)
        rows = np.zeros((uniq.size, spec_rows.shape[1]), np.float32)
        rows[hit] = spec_rows[pos_c[hit]]
        missing = uniq[~hit]
        if missing.size:
            rows[~hit] = _with_retries(
                lambda: store.gather_rows(missing), ctx)
        rows_speculated = int((spec_ids >= 0).sum())
        rows_topped = int((missing >= 0).sum())
        rows_wasted = rows_speculated - int((uniq[hit] >= 0).sum())
        # every fetched row is traffic, used or not (wasted speculation
        # is the price of the overlap and must show up in the account)
        scan_bytes += (rows_speculated + rows_topped) * int(rows.shape[1]) * 4
    else:
        rows = _with_retries(lambda: store.gather_rows(uniq), ctx)
        scan_bytes += int((uniq >= 0).sum()) * int(rows.shape[1]) * 4
    ctx.speculation = {
        "trigger": trigger,
        "rows_speculated": rows_speculated,
        "rows_topped_up": rows_topped,
        "rows_wasted": rows_wasted,
        "failed": spec_failed,
    }
    cand_vecs = rows[inv.reshape(m, r)]  # host scatter back to (m, r, d)
    t_gather = time.perf_counter()
    s, i = rescore(queries, jnp.asarray(cand_vecs), jnp.asarray(cand_idx))

    # live delta rows have no int8 representation: merge them exactly
    # through the same direct-form step the oracle uses (order-invariant)
    for p in store.delta_shards():
        dp = _with_retries(lambda: device_put_partition(p), ctx)
        s, i = direct_step(s, i, queries, dp.vectors, dp.norms,
                           jnp.int32(p.base_index))
        scan_bytes += int(p.vectors.shape[0]) * int(p.vectors.shape[1]) * 4
    ctx.delta_folded = True

    thresh = s[:, plan.k - 1]
    cert = (lb_r1 > thresh) | ~jnp.isfinite(lb_r1)
    ctx.certificate = cert
    out = TopK(s, jnp.where(jnp.isfinite(s), i, -1))

    if not bool(jax.device_get(cert).all()):
        from repro.core.fqsd import streamed_direct_scan

        fb_stats: dict = {}
        exact = streamed_direct_scan(
            queries, _resilient_source(store, "f32", ctx), plan.k,
            prefetch_depth=ctx.prefetch_depth, step_fn=direct_step,
            stream_stats=fb_stats,
            put_retries=ctx.max_retries,
            retry_backoff_s=ctx.retry_backoff_s, health=_ctx_health(ctx),
        )
        # the fallback is a second full pass: its shipped partitions join
        # the transfer account (exactly the case an operator wants to see)
        for key in ("transfers", "restarts"):
            ctx.stream_stats[key] += fb_stats.get(key, 0)
        scan_bytes += int(plan.padded_rows) * int(plan.padded_dim) * 4
        keep = cert[:, None]
        out = TopK(jnp.where(keep, out.scores, exact.scores),
                   jnp.where(keep, out.indices, exact.indices))
    jax.block_until_ready(out.scores)
    ctx.phase_ms = {
        "scan_ms": (t_scan - t_start) * 1e3,
        "gather_ms": (t_gather - t_scan) * 1e3,
        "rescore_ms": (time.perf_counter() - t_gather) * 1e3,
    }
    ctx.bytes_scanned = scan_bytes
    return out


def _int8_streamed(plan, queries, store, ctx) -> TopK:
    """Shared body of the streamed int8 executors (host-RAM and mmap
    shards run the identical schedule; the plan label tells them apart).

    Three phases, bandwidth-first and two-phase-pipelined (paper sections
    3.3 + 5 combined; ISSUE 6 tentpole):

    1. **1 B/element scan** — the int8 tier streams shard by shard through
       the double buffer as multi-array partitions (codes + scales + err +
       exact quantized norms in one prefetch slot), each merged into a
       global widened candidate queue of r+1 certified lower bounds per
       query (r = rescore_factor * k; the +1 entry is the certificate's
       view of the best row OUTSIDE the candidate set). Once a tuned
       fraction of shards (the *speculation trigger*) has merged, a
       snapshot of the queue is handed to a background
       :class:`SpeculativeGather` thread that dedups it and reads its f32
       rows while the device drains the remaining shard steps — the
       random-read gather hides under the scan tail instead of extending
       it.
    2. **candidate-only rescore** — the FINAL queue's r candidate rows per
       query are gathered from the f32 tier (deduplicated random reads;
       for mmap stores these are the only f32 bytes the whole search
       touches): speculated rows are reused by id, only ids the late
       shards added are topped up, and wasted speculative fetches are
       counted into bytes_scanned. The rescore runs the direct-form exact
       distance over exactly the final queue — bit-identical to the
       unspeculated schedule by construction. Live delta rows (no
       quantized representation) merge exactly through the same direct
       step.
    3. **certify or fall back** — a query is certified iff the smallest
       lower bound outside its candidate set strictly exceeds its k-th
       exact candidate distance; uncertified queries are recomputed by the
       streamed direct-form f32 oracle, so the returned top-k is exact
       (values, indices, tie order) for every row either way. Speculation
       never touches the certificate: it reorders reads, not math.

    The certificate lands on ``ctx.certificate``, the double buffer's
    transfer counters on ``ctx.stream_stats``, the honest traffic account
    (codes + per-row channels + candidate reads incl. wasted speculation +
    delta/fallback bytes) on ``ctx.bytes_scanned``, the wall-time split on
    ``ctx.phase_ms``, and the speculation counters on ``ctx.speculation``.
    """
    import time

    t_start = time.perf_counter()
    m = int(queries.shape[0])
    r = _rescore_budget(plan)
    # rescore_factor rides plan.cache_key(); the step caches key on the
    # resolved budget r so differing budgets never share a queue executable.
    # NOTE the pipeline knobs (prefetch depth, speculation trigger) are
    # deliberately absent from every step key: changing them reschedules
    # host work but never recompiles (tested by test_speculation.py).
    bound_step = _cached(("int8-bound-step", r),
                         lambda: make_int8_bound_step(r))

    trigger = ctx.spec_trigger
    if trigger is None:
        trigger = (plan.spec_trigger if plan.spec_trigger >= 0.0
                   else DEFAULT_SPEC_TRIGGER)
    trigger = float(trigger)

    lb = jnp.full((m, r + 1), jnp.inf, jnp.float32)
    li = jnp.full((m, r + 1), -1, jnp.int32)
    stream = DoubleBufferedStream(_resilient_source(store, "int8", ctx),
                                  depth=ctx.prefetch_depth,
                                  put_fn=device_put_partition,
                                  put_retries=ctx.max_retries,
                                  retry_backoff_s=ctx.retry_backoff_s,
                                  health=_ctx_health(ctx))
    n_shards = int(getattr(store, "n_shards", 0) or 0)
    trigger_after = None
    if trigger < 1.0 and n_shards > 1:
        # first shard count at which speculation may launch; must stay
        # < n_shards (speculating after the last shard is just the serial
        # schedule, so the loop condition also guards that)
        trigger_after = max(1, int(np.ceil(trigger * n_shards)))
    spec = None
    shards_done = 0
    scan_bytes = 0
    direct_r1 = None
    for p in stream:
        if isinstance(p, part.PaddedDataset):
            # quarantined int8 shard degraded to its f32 rows: exact
            # distances ARE valid lower bounds of themselves, so merging
            # them into the widened queue through the direct-form step
            # keeps the certificate sound and the result bit-identical
            # to the f32 oracle. Built lazily: the fault-free path never
            # touches this cache entry (no-recompile tests stay exact).
            if direct_r1 is None:
                direct_r1 = _cached(("direct-step", r + 1),
                                    lambda: make_direct_partition_step(r + 1))
            lb, li = direct_r1(lb, li, queries, p.vectors, p.norms,
                               jnp.int32(p.base_index))
            scan_bytes += int(p.vectors.shape[0]) * int(p.vectors.shape[1]) * 4
        else:
            lb, li = bound_step(lb, li, queries, p.q, p.scales, p.err,
                                p.qnorm, jnp.int32(p.base_index))
            scan_bytes += p.scan_bytes()
        shards_done += 1
        if (spec is None and trigger_after is not None
                and trigger_after <= shards_done < n_shards):
            # snapshot the current queue (immutable jax array; the loop
            # keeps producing NEW queues) and let the background thread
            # sync + dedup + gather while the device drains the tail
            spec = SpeculativeGather(li[:, :r], store)
    ctx.stream_stats = {"transfers": stream.transfers,
                        "restarts": stream.restarts}
    return _rescore_certify(plan, queries, store, ctx, lb, li, scan_bytes,
                            spec=spec, t_start=t_start, trigger=trigger)


@register_executor("fqsd-int8-streamed")
def _fqsd_int8_streamed(plan, queries, store, ctx) -> TopK:
    """Streamed quantized FQ-SD over host-RAM shards: 1 B/element scan,
    global widened candidate queue, exact rescore of candidate rows only
    (see :func:`_int8_streamed`)."""
    return _int8_streamed(plan, queries, store, ctx)


@register_executor("fqsd-int8-mmap-streamed")
def _fqsd_int8_mmap_streamed(plan, queries, store, ctx) -> TopK:
    """Manifest-driven streamed quantized FQ-SD over an out-of-core store:
    the int8 codes stream from disk at 1 B/element inside the double
    buffer, and the exact rescore's random mmap reads touch only candidate
    rows of the f32 tier (see :func:`_int8_streamed`) — the paper's
    throughput deployment with its section-5 quantization lever applied to
    the out-of-core path."""
    return _int8_streamed(plan, queries, store, ctx)


@register_executor("fdsq-sharded")
def _fdsq_sharded(plan, queries, dataset: part.PaddedDataset, ctx) -> TopK:
    """Mesh-distributed FD-SQ: replicated query, row-sharded dataset,
    hierarchical O(k) merge."""
    if ctx.mesh is None:
        raise ValueError("plan requires a mesh but ExecContext.mesh is None")
    key = (plan.cache_key(), ctx.mesh, tuple(ctx.mesh_axes))
    fn = _cached(
        key,
        lambda: sh.fdsq_sharded(ctx.mesh, plan.k, plan.metric, tuple(ctx.mesh_axes)),
    )
    return fn(queries, dataset.vectors, dataset.norms)


@register_executor("fqsd-sharded")
def _fqsd_sharded(plan, queries, dataset: part.PaddedDataset, ctx) -> TopK:
    """Mesh-distributed FQ-SD via the query-direction ring: the dataset
    rows stay on their device and the (query block, queue) pairs rotate
    (see repro.core.sharded.fqsd_ring_queries)."""
    if ctx.mesh is None:
        raise ValueError("plan requires a mesh but ExecContext.mesh is None")
    axes = tuple(ctx.mesh_axes)
    query_axis = axes[0]
    model_axis = axes[1] if len(axes) > 1 else None
    key = (plan.cache_key(), ctx.mesh, axes)
    fn = _cached(key, lambda: sh.fqsd_ring_queries(
        ctx.mesh, plan.k, plan.metric, query_axis, model_axis))
    # queries shard over the ring axis: pad the batch to a multiple of it
    m = int(queries.shape[0])
    ring = int(ctx.mesh.shape[query_axis])
    pad = -m % ring
    if pad:
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
    out = fn(queries, dataset.vectors, dataset.norms)
    return TopK(out.scores[:m], out.indices[:m])


@register_executor("fdsq-sharded-int8")
def _fdsq_sharded_int8(plan, queries, dataset: MeshTiered, ctx) -> TopK:
    """Mesh-resident certified int8: the quantized arrays live row-sharded
    over the mesh, every device computes reverse-triangle lower bounds on
    its rows only (1 B/element local traffic) and keeps a widened (m, r+1)
    queue, the queues merge hierarchically with O(r) collective volume
    (repro.core.sharded.fdsq_sharded_int8), and the shared epilogue
    gathers + rescores only candidate f32 rows from the backing store —
    certified or exactly recomputed, bit-identical to the streamed f32
    oracle either way."""
    import time

    if ctx.mesh is None:
        raise ValueError("plan requires a mesh but ExecContext.mesh is None")
    t_start = time.perf_counter()
    r = _rescore_budget(plan)
    key = (plan.cache_key(), ctx.mesh, tuple(ctx.mesh_axes))
    fn = _cached(
        key,
        lambda: sh.fdsq_sharded_int8(ctx.mesh, r, tuple(ctx.mesh_axes)),
    )
    q8 = dataset.quant
    # validity (padding / tombstones / filter mask) rides the exact-norms
    # channel; fold it into qnorm so the mesh scan needs a single channel —
    # runtime data on sharded arrays, never a shape change
    qnorm = jnp.where(jnp.isfinite(q8.norms_sq), q8.qnorm_sq, jnp.inf)
    state = fn(queries, q8.q, q8.scales, q8.err, qnorm)
    n_dev = 1
    for ax in ctx.mesh_axes:
        n_dev *= int(ctx.mesh.shape[ax])
    rows_per_dev = int(q8.q.shape[0]) // n_dev
    per_dev = rows_per_dev * int(q8.q.shape[1]) + 12 * rows_per_dev
    ctx.device_bytes = [per_dev] * n_dev
    return _rescore_certify(plan, queries, dataset.store, ctx,
                            state.scores, state.indices, per_dev * n_dev,
                            t_start=t_start)


def _int8_mesh_streamed(plan, queries, store, ctx) -> TopK:
    """Shared body of the ring-streamed mesh int8 executors (host-RAM and
    mmap shard sources run the identical schedule).

    The paper's FQ-SD stream, fanned out over a device group: shard i of
    the store's int8 source is ``device_put`` to device i mod P (the ring),
    and because the shipped arrays arrive committed to that device, the
    cached bound step that consumes them runs there — P concurrent
    double-buffered scan pipelines out of one host iterator, each advancing
    its own widened (m, r+1) certified lower-bound queue. JAX's async
    dispatch keeps all P devices busy without threads: the host loop only
    enqueues work. One global O(k) merge (host concat of the P queues +
    one lexicographic sort — every device's local top r+1 contains its
    rows' contribution to the global top r+1) and the shared epilogue
    rescores candidate f32 rows exactly as on the single-device path.
    A store larger than the sum of all device memories serves fine: at
    most depth shards are in flight, none resident.

    Per-device scan bytes land on ``ctx.device_bytes``; speculation stays
    off on mesh paths (the scan is already P-way overlapped)."""
    import time

    if ctx.mesh is None:
        raise ValueError("plan requires a mesh but ExecContext.mesh is None")
    t_start = time.perf_counter()
    m = int(queries.shape[0])
    r = _rescore_budget(plan)
    # the SAME step key as the single-device streamed path: one cached
    # wrapper whose jit resolves per-device placements, so mesh adoption
    # adds zero cache entries and repeat searches never miss
    bound_step = _cached(("int8-bound-step", r),
                         lambda: make_int8_bound_step(r))
    devices = list(ctx.mesh.devices.flat)
    n_dev = len(devices)
    qs = [jax.device_put(queries, d) for d in devices]
    lb0 = np.full((m, r + 1), np.inf, np.float32)
    li0 = np.full((m, r + 1), -1, np.int32)
    lbs = [jax.device_put(lb0, d) for d in devices]
    lis = [jax.device_put(li0, d) for d in devices]
    # each device's step runs under a one-device mesh of its own: inside an
    # ambient jax.set_mesh over the whole group, jit refuses arguments
    # committed to a single device of it (incompatible devices), and
    # without one the scope is a no-op
    dev_scopes = [jax.sharding.Mesh(np.array([dev]), ("data",))
                  for dev in devices]
    ring = make_ring_put(devices)
    # prefetch at least one shard per device so the ring never starves
    stream = DoubleBufferedStream(
        _resilient_source(store, "int8", ctx),
        depth=max(ctx.prefetch_depth, n_dev),
        put_fn=lambda p: device_put_partition(p, put_fn=ring),
        put_retries=ctx.max_retries, retry_backoff_s=ctx.retry_backoff_s,
        health=_ctx_health(ctx),
    )
    dev_bytes = [0] * n_dev
    shard_i = 0
    direct_r1 = None
    for p in stream:
        d = shard_i % n_dev  # consumption order == ring put order
        if isinstance(p, part.PaddedDataset):
            # quarantined shard's f32 rows, already ring-committed to
            # device d (the resilient source yields before the ring put,
            # so skipped shards never desync put and consume order);
            # exact distances merge as their own lower bounds — see
            # _int8_streamed
            if direct_r1 is None:
                direct_r1 = _cached(("direct-step", r + 1),
                                    lambda: make_direct_partition_step(r + 1))
            with jax.set_mesh(dev_scopes[d]):
                lbs[d], lis[d] = direct_r1(lbs[d], lis[d], qs[d], p.vectors,
                                           p.norms, jnp.int32(p.base_index))
            dev_bytes[d] += (int(p.vectors.shape[0])
                             * int(p.vectors.shape[1]) * 4)
        else:
            with jax.set_mesh(dev_scopes[d]):
                lbs[d], lis[d] = bound_step(lbs[d], lis[d], qs[d], p.q,
                                            p.scales, p.err, p.qnorm,
                                            jnp.int32(p.base_index))
            dev_bytes[d] += p.scan_bytes()
        shard_i += 1
    ctx.stream_stats = {"transfers": stream.transfers,
                        "restarts": stream.restarts}
    ctx.device_bytes = dev_bytes
    # global merge: concat the P per-device queues on host, one two-key
    # sort, keep r+1 — O(k) traffic per device, independent of store size
    all_s = np.concatenate([np.asarray(x) for x in lbs], axis=1)
    all_i = np.concatenate([np.asarray(x) for x in lis], axis=1)
    s, i = sort_pairs(jnp.asarray(all_s), jnp.asarray(all_i))
    return _rescore_certify(plan, queries, store, ctx,
                            s[:, : r + 1], i[:, : r + 1], sum(dev_bytes),
                            t_start=t_start)


@register_executor("fqsd-sharded-int8")
def _fqsd_sharded_int8(plan, queries, store, ctx) -> TopK:
    """Ring-streamed mesh int8 over host-RAM shards: shard i scans on
    device i mod P, per-device widened queues, one global O(k) merge,
    candidate-only f32 rescore (see :func:`_int8_mesh_streamed`)."""
    return _int8_mesh_streamed(plan, queries, store, ctx)


@register_executor("fqsd-sharded-int8-streamed")
def _fqsd_sharded_int8_streamed(plan, queries, store, ctx) -> TopK:
    """Ring-streamed mesh int8 over an out-of-core (mmap) store: the codes
    leave the disk inside each ring device_put at 1 B/element, so one store
    can exceed the memory of ALL devices combined
    (see :func:`_int8_mesh_streamed`)."""
    return _int8_mesh_streamed(plan, queries, store, ctx)
