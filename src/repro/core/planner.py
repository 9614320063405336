"""Query planning — pure "decide the plan" layer of the engine.

The paper's central observation is that FD-SQ (latency) and FQ-SD
(throughput) are two *logical* configurations of one physical FPGA
configuration: choosing between them is a scheduling decision, not a
hardware change. This module is that decision, isolated as a pure
function:

    plan(query_shape, dataset_meta, engine_cfg, mode) -> ExecutionPlan

An :class:`ExecutionPlan` is frozen, hashable, deterministic data — it
names the executor (see ``repro.core.executors``), the resolved dataset
chunking, and the padding geometry. Executors key their compiled
executables on plans, which makes the paper's "no reflashing" invariant
testable: planning the same shapes twice yields equal plans, and equal
plans hit the same cached executable no matter how many mode switches
happened in between (section 3.2).

Nothing here touches device state; everything here is unit-testable
without JAX tracing. The one external fact a plan may carry is the
*autotuned block shapes* for the fused Pallas executors: ``plan()``
consults the per-device tuning cache (``repro.tuning``, a pure read of
deterministic data — a cold cache simply leaves the blocks at 0 = kernel
defaults), and the chosen blocks ride ``cache_key()`` so tuned plans hit
the same compiled executable forever after.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Sequence

from repro.tuning.autotune import lookup_blocks, lookup_pipeline

Backend = Literal["xla", "pallas"]
ModeHint = Literal["fdsq", "fqsd", "fqsd-streamed"]

#: Executors the planner may select (must match the registry in
#: repro.core.executors — asserted by tests/test_planner.py).
PLANNABLE_EXECUTORS = (
    "fdsq-xla",
    "fqsd-xla",
    "fdsq-pallas",
    "fqsd-streamed",
    "fqsd-mmap-streamed",
    "fqsd-int8",
    "fqsd-int8-pallas",
    "fqsd-int8-streamed",
    "fqsd-int8-mmap-streamed",
    "fdsq-sharded",
    "fqsd-sharded",
    "fdsq-sharded-int8",
    "fqsd-sharded-int8",
    "fqsd-sharded-int8-streamed",
)

#: Executors whose block shapes the per-device autotuner may override.
TUNABLE_EXECUTORS = ("fdsq-pallas", "fqsd-int8-pallas")

#: Streamed executors whose pipeline knobs (prefetch depth, speculation
#: trigger, rescore budget) the end-to-end autotuner may override.
PIPELINE_TUNABLE_EXECUTORS = ("fqsd-int8-streamed", "fqsd-int8-mmap-streamed",
                              "fqsd-sharded-int8",
                              "fqsd-sharded-int8-streamed")


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """Resolved logical configuration — logged for observability / tests."""

    mode: str  # "fdsq" | "fqsd" | "fqsd-streamed" | "fdsq-sharded" | ...
    backend: str
    m: int
    k: int
    metric: str
    chunk_rows: int
    n_partitions: int


@dataclasses.dataclass(frozen=True)
class ExecutionPlan(EnginePlan):
    """EnginePlan + the physical decisions: executor, chunking, padding,
    and the storage tier the scan reads (f32 = 4 B/elem, int8 = 1 B/elem)."""

    executor: str = "fdsq-xla"
    padded_rows: int = 0
    padded_dim: int = 0
    n_valid: int = 0
    sharded: bool = False
    tier: str = "f32"
    rescore_factor: int = 4  # int8 tier: exact-rescore budget = factor * k
    n_shards: int = 1
    #: Autotuned kernel tile shapes for the fused executors; 0 means "use
    #: the kernel defaults" (cold tuning cache). See repro.tuning.
    block_m: int = 0
    block_n: int = 0
    block_d: int = 0
    #: Autotuned pipeline knobs for the streamed executors; 0 / -1.0 mean
    #: "unset" (the engine resolves its own defaults). Both ride the cache
    #: key so a tuned plan is distinguishable from an untuned one — but the
    #: streamed executors key their compiled steps on (kind, k/r) only, so
    #: changing either knob never recompiles (tested).
    prefetch_depth: int = 0
    spec_trigger: float = -1.0

    def cache_key(self) -> tuple:
        """Everything that determines the compiled executable for this plan
        (query batch m and padding geometry included; log-only fields not)."""
        return (
            self.executor, self.m, self.k, self.metric, self.chunk_rows,
            self.n_partitions, self.padded_rows, self.padded_dim,
            self.tier, self.rescore_factor,
            self.block_m, self.block_n, self.block_d,
            self.prefetch_depth, self.spec_trigger,
        )


@dataclasses.dataclass(frozen=True)
class DatasetMeta:
    """Shape facts about the (padded) dataset a plan will run against."""

    padded_rows: int
    padded_dim: int
    n_valid: int
    sharded: bool = False
    resident: bool = True  # False => host-streamed partitions


@dataclasses.dataclass(frozen=True)
class DatasetStoreMeta(DatasetMeta):
    """DatasetMeta + what a DatasetStore knows: the dtype tier the scan
    should read, the shard layout, and whether shards are mmap-backed files
    (out-of-core) — the storage facts the planner turns into executor
    choices (pure data; the store itself never reaches the planner)."""

    tier: str = "f32"  # "f32" | "int8" (int8 => certified exact rescore)
    n_shards: int = 1
    rows_per_shard: int = 0
    mmap: bool = False  # shards are memmap files, not host RAM


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The engine's constructor knobs as pure data (planner input)."""

    k: int
    metric: str = "l2"
    backend: str = "xla"
    chunk_rows: int = 8192
    n_partitions: int = 8
    sharded: bool = False
    mesh_axes: Sequence[str] = ("data", "model")
    rescore_factor: int = 4  # int8 tier exact-rescore budget (x k)
    #: True when the engine's rescore_factor was set explicitly by the
    #: caller: the pipeline autotuner must not override a pinned budget.
    rescore_pinned: bool = False
    dtype: str = "float32"  # query/dataset dtype (part of the tuning key)


def largest_divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of `n` that is <= `cap` (>= 1 for any n >= 1).

    Replaces the former ``while n % chunk: chunk //= 2`` loop, which only
    visited halvings of the requested chunk and could degrade to chunk=1
    (a per-row scan) — or never terminate for cap <= 0 — whenever the
    padded row count shared no power-of-two suffix with the request.
    O(sqrt n) divisor walk; n is a row count, so this is microseconds.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    cap = min(cap, n)
    if cap < 1:
        return 1
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            if d <= cap:
                best = max(best, d)
            co = n // d
            if co <= cap:
                best = max(best, co)
        d += 1
    return best


class PlanRefused(ValueError):
    """The request names a plan that cannot run as asked (a fused kernel
    whose tiles do not fit on-chip memory). Raised instead of quietly
    serving another executor: the caller picks the alternative."""


def _check_fused_fits(executor: str, k: int, d: int, blocks, rescore_factor):
    """Refuse a fused-kernel plan whose tiles cannot fit the kernel's VMEM
    (estimate and limit: ``repro.kernels.knn.ops.vmem_bytes``). Mosaic
    would otherwise reject it at the first search, not at planning."""
    from repro.kernels.knn import ops as knn_ops

    defaults = knn_ops.DEFAULT_BLOCKS
    bm, bn, bd = (b or dflt for b, dflt in zip(blocks, defaults))
    need = knn_ops.vmem_bytes(k, d, bm, bn, bd, rescore_factor)
    if need > knn_ops.VMEM_LIMIT_BYTES:
        queue = knn_ops.queue_width(k, rescore_factor)
        raise PlanRefused(
            f"{executor} refused: k={k}"
            + ("" if rescore_factor is None
               else f" with rescore_factor={rescore_factor}")
            + f" needs a {queue}-wide on-chip queue; its tiles need about "
            f"{need / 2**20:.1f} MiB of VMEM, over the "
            f"{knn_ops.VMEM_LIMIT_BYTES / 2**20:.0f} MiB a kernel gets. "
            "Use backend='xla', a smaller k, or a smaller rescore_factor.")


def plan(
    query_shape: Sequence[int],
    dataset_meta: DatasetMeta,
    engine_cfg: EngineConfig,
    mode: ModeHint = "fdsq",
    stream_rows: int | None = None,
    k: int | None = None,
    metric: str | None = None,
    **unknown,
) -> ExecutionPlan:
    """Pure planning function: shapes + config in, ExecutionPlan out.

    ``k`` and ``metric`` are *per-request* overrides of the engine config
    (the request-first API: every option is a fact of the request the
    planner normalizes). They ride ``ExecutionPlan.cache_key()`` — and the
    autotune lookup key — so per-request values hit exactly the executables
    a dedicated engine with those values would have compiled.

    Unknown keyword arguments are rejected loudly: a typo'd option must
    fail the call, not silently plan something else.

    Replaces the inline ``if mesh / if backend == "pallas"`` branches that
    used to live in ``ExactKNN.query`` / ``query_batch``:

    * non-resident dataset -> the streamed executors: manifest-driven
      "fqsd-mmap-streamed" when the meta is a DatasetStoreMeta (shards on
      disk or host, scanned through the double buffer), the legacy
      host-iterator "fqsd-streamed" otherwise. A store-backed non-resident
      plan with tier="int8" (l2 only) KEEPS the quantized tier: the scan
      streams 1 B/element codes and the certified rescore reads only
      candidate rows of the f32 tier — "fqsd-int8-mmap-streamed" for mmap
      shards, "fqsd-int8-streamed" for host-RAM shards;
    * sharded dataset  -> the mesh executors (mode picks fan-out vs ring);
    * tier="int8"      -> the 1 B/element quantized scan with certified
      exact rescore: the fused on-chip kernel "fqsd-int8-pallas" when
      backend="pallas", the XLA "fqsd-int8" otherwise (l2 only — other
      metrics fall back to the f32 executors);
    * backend="pallas" -> the fused kernel, which serves BOTH logical modes
      AND all three metrics with one executable family ("fdsq-pallas";
      cos is served by pre-normalized rows through the ip epilogue);
    * mode="fqsd"      -> chunked scan with a chunk size that is a real
      divisor of the padded row count (see `largest_divisor_at_most`);
    * mode="fdsq"      -> partition-parallel fan-out with a partition count
      that divides the padded rows.
    """
    if unknown:
        raise TypeError(
            "plan() got unexpected keyword argument(s): "
            + ", ".join(repr(key) for key in sorted(unknown))
        )
    if mode not in ("fdsq", "fqsd", "fqsd-streamed"):
        raise ValueError(f"unknown mode hint {mode!r}")
    if len(query_shape) == 2:
        m = int(query_shape[0])
    elif len(query_shape) == 1:
        m = 1
    else:
        raise ValueError(f"query_shape must be (m, d) or (d,), got {query_shape}")

    cfg = engine_cfg
    k = int(cfg.k) if k is None else int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    metric = cfg.metric if metric is None else metric
    sharded = bool(cfg.sharded or dataset_meta.sharded)
    rows = int(dataset_meta.padded_rows)
    chunk = int(cfg.chunk_rows)
    n_parts = int(cfg.n_partitions)
    mode_label = mode
    store_backed = isinstance(dataset_meta, DatasetStoreMeta)
    tier = dataset_meta.tier if store_backed else "f32"

    if mode == "fqsd-streamed" or not dataset_meta.resident:
        if store_backed and tier == "int8" and metric == "l2":
            if sharded:
                # cluster-scale throughput deployment: the int8 shard
                # source ring-streams over the mesh devices (shard i ->
                # device i mod P), one global O(k) merge + candidate-only
                # rescore — a store may exceed ALL device memories combined
                executor = ("fqsd-sharded-int8-streamed" if dataset_meta.mmap
                            else "fqsd-sharded-int8")
                mode_label = "fqsd-sharded-int8"
            else:
                # the paper's throughput deployment: out-of-core scan at
                # 1 B/element with certified rescore reads of candidate rows
                executor = ("fqsd-int8-mmap-streamed" if dataset_meta.mmap
                            else "fqsd-int8-streamed")
                mode_label = "fqsd-int8-streamed"
        else:
            # mesh non-resident f32 plans also land here: the single-device
            # manifest stream serves them exactly (only the int8 tier has a
            # mesh streaming schedule — it is the bandwidth-bound one)
            executor = "fqsd-mmap-streamed" if store_backed else "fqsd-streamed"
            mode_label = "fqsd-streamed"
            tier = "f32"  # exact base tier (int8 needs a store + l2)
        if stream_rows is not None:
            chunk = int(stream_rows)
        elif store_backed and dataset_meta.rows_per_shard:
            chunk = int(dataset_meta.rows_per_shard)
    elif sharded:
        if store_backed and tier == "int8" and metric == "l2":
            # mesh-resident certified int8: row-sharded quantized arrays,
            # per-device widened queues, hierarchical O(r) merge; rescore
            # reads only candidate f32 rows of the backing store
            executor = "fdsq-sharded-int8"
            mode_label = "fdsq-sharded-int8"
        else:
            executor = "fdsq-sharded" if mode == "fdsq" else "fqsd-sharded"
            mode_label = f"{mode}-sharded"
            tier = "f32"
    elif tier == "int8" and mode == "fqsd" and metric == "l2":
        executor = ("fqsd-int8-pallas" if cfg.backend == "pallas"
                    else "fqsd-int8")
        mode_label = "fqsd-int8"
        # chunking doubles as the f32 fallback geometry for uncertified rows
        chunk = largest_divisor_at_most(rows, max(1, chunk))
    elif cfg.backend == "pallas":
        executor = "fdsq-pallas"
        tier = "f32"
    elif mode == "fdsq":
        executor = "fdsq-xla"
        tier = "f32"
        n_parts = largest_divisor_at_most(rows, max(1, n_parts))
    else:
        executor = "fqsd-xla"
        tier = "f32"
        chunk = largest_divisor_at_most(rows, max(1, chunk))

    # tuned end-to-end pipeline knobs for the streamed executors (pure
    # cache read, same contract as the block lookup below). The tuned
    # rescore budget applies only when the engine's own budget is not
    # pinned by the caller (cfg.rescore_pinned).
    rescore_factor = int(cfg.rescore_factor)
    prefetch_depth = 0
    spec_trigger = -1.0
    if executor in PIPELINE_TUNABLE_EXECUTORS:
        knobs = lookup_pipeline(executor, m, rows,
                                int(dataset_meta.padded_dim),
                                cfg.dtype, metric, k)
        if knobs is not None:
            prefetch_depth = int(knobs.prefetch_depth)
            spec_trigger = float(knobs.spec_trigger)
            if not cfg.rescore_pinned:
                rescore_factor = int(knobs.rescore_factor)

    # per-device autotuned tile shapes for the fused kernels (0 = kernel
    # defaults). The lookup is a pure read of the persisted tuning cache:
    # equal inputs + equal cache state -> equal plans -> executable cache
    # hits, so tuning never causes a recompile for a seen key.
    block_m = block_n = block_d = 0
    if executor in TUNABLE_EXECUTORS:
        # the int8 kernel's queue width also depends on the rescore budget,
        # so its tuned blocks are keyed per rescore_factor (autotune.py)
        tuned = lookup_blocks(
            executor, m, rows, int(dataset_meta.padded_dim),
            cfg.dtype, metric, k,
            int(cfg.rescore_factor) if executor == "fqsd-int8-pallas"
            else None,
        )
        if tuned is not None:
            block_m, block_n, block_d = tuned
        _check_fused_fits(executor, k, int(dataset_meta.padded_dim),
                          (block_m, block_n, block_d),
                          int(cfg.rescore_factor)
                          if executor == "fqsd-int8-pallas" else None)

    return ExecutionPlan(
        mode=mode_label,
        backend=cfg.backend,
        m=m,
        k=k,
        metric=metric,
        chunk_rows=chunk,
        n_partitions=n_parts,
        executor=executor,
        padded_rows=rows,
        padded_dim=int(dataset_meta.padded_dim),
        n_valid=int(dataset_meta.n_valid),
        sharded=sharded,
        tier=tier,
        rescore_factor=rescore_factor,
        n_shards=int(getattr(dataset_meta, "n_shards", 1)),
        block_m=block_m,
        block_n=block_n,
        block_d=block_d,
        prefetch_depth=prefetch_depth,
        spec_trigger=spec_trigger,
    )
