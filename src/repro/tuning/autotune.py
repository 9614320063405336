"""Per-device block-shape autotuner for the fused Pallas kernels.

The fused kNN kernels are tiled by (block_m, block_n, block_d); the right
tile shapes depend on the device (VMEM size, MXU/VPU width, interpret-mode
CPU) and on the problem key (M, N, d, dtype, metric). Instead of baked-in
constants, this module:

1. enumerates the *legal* candidate shapes for a key
   (:func:`candidate_blocks` — alignment + VMEM-budget filtered);
2. times each candidate on the live device (:func:`autotune_knn`; off-TPU
   the kernels run in interpret mode, so the sweep still works on CPU —
   the timings then rank the interpreter, which is exactly what serves
   local tests);
3. persists the winner to a JSON cache under ``artifacts/autotune/`` keyed
   by device kind, and
4. answers the planner's pure lookup (:func:`lookup_blocks`) so
   ``ExecutionPlan`` carries tuned blocks instead of constants.

The planner only ever *reads* the cache (a cold cache falls back to the
kernel defaults), so planning stays pure and cheap; sweeps are explicit
offline/benchmark-time calls. Because tuned blocks ride the plan's
``cache_key()``, a cache hit reproduces the exact previous plan and the
executor layer's executable cache guarantees zero recompiles
("no reflashing" extends to tuning).

Cache key format (one line per entry in the JSON file):

    <kernel>|m<pow2-bucketed batch>|n<padded rows>|d<padded dim>|<dtype>|<metric>|k<k>[|r<rescore_factor>]

(the |r field appears only on int8-kernel keys; both k and the rescore
factor set the on-chip queue width, so each gets its own tuning entry).
M is bucketed to the next power of two — the serving layer already pads
batches that way, so tuning inherits the same O(log max_batch) key space.

Beyond per-kernel block shapes, the same cache persists one more entry
kind (distinguished by key prefix, validated per-kind on load):

    pipe|<executor>|m<pow2>|n<rows>|d<dim>|<dtype>|<metric>|k<k>
        -> PipelineKnobs(prefetch_depth, spec_trigger, rescore_factor,
           rows_per_shard) — the end-to-end winner of
        :func:`autotune_pipeline` for one streamed-int8 problem; consumed
        by ``planner.plan()`` so streamed plans carry tuned pipeline knobs.

See ``src/repro/tuning/README.md`` for the sweep spaces and how to
pre-seed caches for CI.
"""
from __future__ import annotations

import json
import os
import re
from typing import NamedTuple

SCHEMA_VERSION = 1
DEFAULT_CACHE_DIR = os.path.join("artifacts", "autotune")

#: Sweep space (filtered per key by :func:`candidate_blocks`).
BM_CANDIDATES = (8, 32, 128, 256)
BN_CANDIDATES = (256, 512, 1024, 2048)
BD_CANDIDATES = (128, 256, 512)


class BlockShapes(NamedTuple):
    block_m: int
    block_n: int
    block_d: int


class PipelineKnobs(NamedTuple):
    """End-to-end pipeline knobs for one streamed-int8 problem.

    prefetch_depth   DoubleBufferedStream depth (host->device overlap)
    spec_trigger     shard fraction after which the candidate gather is
                     speculatively started on a background thread
    rescore_factor   candidate budget multiplier (r = factor * k)
    rows_per_shard   advisory shard size for store builds; the planner
                     cannot re-shard an existing store, so this field is
                     only applied when *building* one (see tuning README)
    """

    prefetch_depth: int
    spec_trigger: float
    rescore_factor: int
    rows_per_shard: int


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def tuning_key(kernel: str, m: int, n: int, d: int, dtype: str,
               metric: str, k: int, rescore_factor: int | None = None) -> str:
    """Stable string key for one tuning problem (see module docstring).

    `k` is part of the key because it sets the on-chip queue width, which
    both constrains legal block_n and changes the winning trade-off —
    blocks tuned at one k must never be applied (and silently re-clamped)
    under another. `rescore_factor` joins the key for the int8 kernel
    (None for f32) for the same reason: the queue width is
    2 * next_pow2(rescore_factor * k_eff), so a winner swept at one budget
    would otherwise be re-clamped past the vetted VMEM legality under
    another.
    """
    from repro.core.partition import next_pow2

    key = (f"{kernel}|m{next_pow2(max(1, int(m)))}|n{int(n)}|d{int(d)}"
           f"|{dtype}|{metric}|k{int(k)}")
    if rescore_factor is not None:
        key += f"|r{int(rescore_factor)}"
    return key


def pipeline_key(executor: str, m: int, n: int, d: int, dtype: str,
                 metric: str, k: int) -> str:
    """Stable key for one end-to-end streamed-pipeline tuning problem.

    Keyed on the *executor* (not a kernel): the sweep times whole searches,
    so the winner is only transferable to plans that run the same executor
    on the same planner-visible geometry. rescore_factor is NOT part of
    the key — it is one of the swept knobs, stored in the entry value.
    """
    from repro.core.partition import next_pow2

    return (f"pipe|{executor}|m{next_pow2(max(1, int(m)))}|n{int(n)}"
            f"|d{int(d)}|{dtype}|{metric}|k{int(k)}")


def _validate_entry(key: str, e: dict) -> None:
    """Raise if one cache entry is malformed for its kind (prefix-typed)."""
    if key.startswith("pipe|"):
        PipelineKnobs(int(e["prefetch_depth"]), float(e["spec_trigger"]),
                      int(e["rescore_factor"]), int(e["rows_per_shard"]))
    else:
        BlockShapes(int(e["block_m"]), int(e["block_n"]), int(e["block_d"]))


def device_kind() -> str:
    """Live device kind ("cpu", "TPU v5e", ...), filesystem-sanitized."""
    import jax

    kind = jax.devices()[0].device_kind
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", kind).strip("_") or "unknown"


class AutotuneCache:
    """JSON-persisted {tuning key -> winning BlockShapes} map.

    Loading is tolerant by design: a missing, corrupted, or wrong-schema
    file yields an empty cache (the planner then falls back to kernel
    defaults) and the next :meth:`put` rewrites it cleanly — a damaged
    cache can never take serving down, only un-tune it.
    """

    def __init__(self, path: str | None = None):
        self.path = path
        self._entries: dict[str, dict] = {}
        self._loaded = path is None

    @classmethod
    def for_device(cls, cache_dir: str = DEFAULT_CACHE_DIR) -> "AutotuneCache":
        return cls(os.path.join(cache_dir, f"{device_kind()}.json"))

    # ------------------------------------------------------------- storage
    def load(self) -> "AutotuneCache":
        self._loaded = True
        self._entries = {}
        if self.path is None or not os.path.exists(self.path):
            return self
        try:
            with open(self.path) as f:
                payload = json.load(f)
            entries = payload["entries"]
            if not isinstance(entries, dict):
                raise TypeError("entries must be a dict")
            ok: dict[str, dict] = {}
            for key, e in entries.items():
                # validate eagerly (per kind) so one bad entry cannot
                # poison lookups; a bad entry is dropped, not fatal
                try:
                    _validate_entry(key, e)
                except (ValueError, KeyError, TypeError):
                    continue
                ok[key] = dict(e)
            self._entries = ok
        except (OSError, ValueError, KeyError, TypeError):
            self._entries = {}  # corrupt cache == cold cache, never an error
        return self

    def save(self) -> None:
        if self.path is None:
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        payload = {
            "schema_version": SCHEMA_VERSION,
            "device": os.path.splitext(os.path.basename(self.path))[0],
            "entries": self._entries,
        }
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        os.replace(tmp, self.path)

    # -------------------------------------------------------------- access
    def _ensure(self) -> None:
        if not self._loaded:
            self.load()

    def get(self, key: str) -> BlockShapes | None:
        self._ensure()
        e = self._entries.get(key)
        if e is None or "block_m" not in e:
            return None
        return BlockShapes(int(e["block_m"]), int(e["block_n"]),
                           int(e["block_d"]))

    def put(self, key: str, blocks: BlockShapes, **meta) -> None:
        self._ensure()
        self._entries[key] = {
            "block_m": int(blocks.block_m),
            "block_n": int(blocks.block_n),
            "block_d": int(blocks.block_d),
            **meta,
        }
        self.save()

    def get_pipeline(self, key: str) -> PipelineKnobs | None:
        self._ensure()
        e = self._entries.get(key)
        if e is None or "prefetch_depth" not in e:
            return None
        return PipelineKnobs(int(e["prefetch_depth"]),
                             float(e["spec_trigger"]),
                             int(e["rescore_factor"]),
                             int(e["rows_per_shard"]))

    def put_pipeline(self, key: str, knobs: PipelineKnobs, **meta) -> None:
        self._ensure()
        self._entries[key] = {
            "prefetch_depth": int(knobs.prefetch_depth),
            "spec_trigger": float(knobs.spec_trigger),
            "rescore_factor": int(knobs.rescore_factor),
            "rows_per_shard": int(knobs.rows_per_shard),
            **meta,
        }
        self.save()

    def __len__(self) -> int:
        self._ensure()
        return len(self._entries)

    def keys(self):
        self._ensure()
        return tuple(self._entries)


# ------------------------------------------------------- default instance
_default_cache: AutotuneCache | None = None


def default_cache() -> AutotuneCache:
    """Process-wide cache for the live device (lazy; used by the planner)."""
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache.for_device()
    return _default_cache


def set_default_cache(cache: AutotuneCache | None) -> None:
    """Swap the planner-visible cache (tests; None resets to lazy default)."""
    global _default_cache
    _default_cache = cache


def lookup_blocks(kernel: str, m: int, n: int, d: int, dtype: str,
                  metric: str, k: int,
                  rescore_factor: int | None = None) -> BlockShapes | None:
    """Pure read the planner calls: tuned blocks for a key, else None.

    Never raises — a broken cache (or a device-less environment) must not
    break planning; it only costs the tuning.
    """
    try:
        return default_cache().get(
            tuning_key(kernel, m, n, d, dtype, metric, k, rescore_factor)
        )
    except Exception:
        return None


def lookup_pipeline(executor: str, m: int, n: int, d: int, dtype: str,
                    metric: str, k: int) -> PipelineKnobs | None:
    """Pure read the planner calls: tuned pipeline knobs, else None.

    Same contract as :func:`lookup_blocks` — never raises.
    """
    try:
        return default_cache().get_pipeline(
            pipeline_key(executor, m, n, d, dtype, metric, k)
        )
    except Exception:
        return None


# --------------------------------------------------------------- sweeping
def candidate_blocks(
    m: int,
    n: int,
    d: int,
    k: int,
    rescore_factor: int | None = None,
) -> list[BlockShapes]:
    """Legal (bm, bn, bd) sweep for one problem (ops.py pads to any of
    these, so legality = the tile holds the on-chip queue, bd stays within
    the padded dim, and the kernel's VMEM estimate fits).

    ``rescore_factor=None`` sweeps the f32 kernel (queue width k_eff); an
    int sweeps the int8 kernel (queue width 2 x its rescore budget). VMEM
    is judged by the same estimate and limit the planner refuses plans
    with (``kernels.knn.ops.vmem_bytes`` / ``VMEM_LIMIT_BYTES``), so every
    candidate the sweep can persist is one the planner will serve. Empty
    when no tile fits.
    """
    from repro.kernels.knn import ops as knn_ops

    queue_len = knn_ops.queue_width(k, rescore_factor)
    d_pad = _round_up(max(1, d), 128)
    out: list[BlockShapes] = []
    for bm in BM_CANDIDATES:
        if bm > 2 * _round_up(max(1, m), 8):
            continue  # all-padding m tiles are pure waste
        for bn in BN_CANDIDATES:
            if bn < queue_len or bn > 2 * _round_up(max(1, n), 256):
                continue
            for bd in BD_CANDIDATES:
                if bd > d_pad:
                    continue
                if (knn_ops.vmem_bytes(k, d, bm, bn, bd, rescore_factor)
                        <= knn_ops.VMEM_LIMIT_BYTES):
                    out.append(BlockShapes(bm, bn, bd))
    return out


def _time_call(fn, *args, repeats: int = 2) -> float:
    import time

    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def autotune_knn(
    m: int,
    n: int,
    d: int,
    k: int = 10,
    metric: str = "l2",
    dtype: str = "float32",
    tier: str = "f32",
    rescore_factor: int = 4,
    cache: AutotuneCache | None = None,
    repeats: int = 2,
    max_candidates: int | None = None,
    seed: int = 0,
) -> tuple[BlockShapes, dict]:
    """Sweep legal block shapes for one key on the live device and persist
    the winner. Returns (winner, {candidate repr -> median seconds}).

    Pass the PLANNER-VISIBLE geometry — m = plan.m (the padded batch),
    n = plan.padded_rows, d = plan.padded_dim — so the stored key is the
    one ``planner.plan()`` will look up (``ExactKNN.plan_for`` exposes it;
    the kernels re-pad internally, so padded sizes are valid sweep sizes).

    tier="f32" tunes the fused kernel behind the "fdsq-pallas" executor;
    tier="int8" tunes "fqsd-int8-pallas" (the key's kernel field follows
    the executor name, so the planner's lookups match by construction).
    """
    import functools

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.knn import ops as knn_ops

    if cache is None:
        cache = default_cache()
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((m, d)), dtype=dtype)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=dtype)

    if tier == "int8":
        from repro.core.quantized import quantize_dataset

        kernel = "fqsd-int8-pallas"
        ds = quantize_dataset(x)

        def run(blocks: BlockShapes):
            fn = functools.partial(
                knn_ops.knn_int8, k=k, rescore_factor=rescore_factor,
                block_m=blocks.block_m, block_n=blocks.block_n,
                block_d=blocks.block_d,
            )
            return _time_call(fn, q, ds, x.astype(jnp.float32),
                              repeats=repeats)
    elif tier == "f32":
        kernel = "fdsq-pallas"

        def run(blocks: BlockShapes):
            fn = functools.partial(
                knn_ops.knn, k=k, metric=metric,
                block_m=blocks.block_m, block_n=blocks.block_n,
                block_d=blocks.block_d,
            )
            return _time_call(fn, q, x, repeats=repeats)
    else:
        raise ValueError(f"unknown tier {tier!r}; known: f32, int8")

    key_factor = rescore_factor if tier == "int8" else None
    cands = candidate_blocks(m, n, d, k, key_factor)
    if not cands:
        raise ValueError(
            f"no ({kernel}) tile holds the {knn_ops.queue_width(k, key_factor)}"
            f"-wide on-chip queue within "
            f"{knn_ops.VMEM_LIMIT_BYTES / 2**20:.0f} MiB of VMEM")
    if max_candidates is not None:
        cands = cands[:max_candidates]
    timings: dict[str, float] = {}
    best: BlockShapes | None = None
    best_t = float("inf")
    for blocks in cands:
        t = run(blocks)
        timings[f"{blocks.block_m}x{blocks.block_n}x{blocks.block_d}"] = t
        if t < best_t:
            best, best_t = blocks, t
    assert best is not None  # cands is not empty
    cache.put(
        tuning_key(kernel, m, n, d, dtype, metric, k, key_factor), best,
        us_per_call=best_t * 1e6, n_candidates=len(cands),
    )
    return best, timings


# ------------------------------------------------- end-to-end pipeline sweep
#: Pipeline sweep space (small by design: each point is a whole timed
#: search over a freshly built store, not one kernel call).
PIPE_PREFETCH_CANDIDATES = (1, 2, 4)
PIPE_TRIGGER_CANDIDATES = (0.25, 0.5, 0.75, 1.0)
PIPE_RESCORE_CANDIDATES = (2, 4, 8)


def autotune_pipeline(
    m: int,
    n: int,
    d: int,
    k: int = 10,
    metric: str = "l2",
    cache: AutotuneCache | None = None,
    repeats: int = 2,
    prefetch_candidates: tuple[int, ...] = PIPE_PREFETCH_CANDIDATES,
    trigger_candidates: tuple[float, ...] = PIPE_TRIGGER_CANDIDATES,
    rescore_candidates: tuple[int, ...] = PIPE_RESCORE_CANDIDATES,
    shard_candidates: tuple[int, ...] | None = None,
    directory: str | None = None,
    seed: int = 0,
) -> tuple[PipelineKnobs, dict]:
    """End-to-end sweep of the streamed-int8 pipeline knobs on the live
    device: build a synthetic store per shard-size candidate, time whole
    ``search()`` calls per (prefetch_depth, spec_trigger, rescore_factor)
    combination, and persist the winner under :func:`pipeline_key`.

    Returns (winner, {candidate repr -> median seconds}).

    The winner is persisted for *both* streamed int8 executors
    (``fqsd-int8-streamed`` and ``fqsd-int8-mmap-streamed``): the knobs
    describe the scan/gather overlap, which transfers across backing
    stores; the mirrored entry is tagged ``mirrored=True``. Default shard
    candidates are exact divisors of n (multiples of 128), so the swept
    store keeps ``padded_rows == n`` and the stored key is the one the
    planner looks up for a production store of the same geometry.
    ``rows_per_shard`` in the winner is *advisory* — the planner cannot
    re-shard an existing store, it is applied when building one.
    """
    import time

    import numpy as np

    from repro.api.types import SearchRequest
    from repro.core.engine import ExactKNN
    from repro.store import DatasetStore

    if metric != "l2":
        raise ValueError("the streamed int8 pipeline serves l2 only")
    if cache is None:
        cache = default_cache()
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((m, d)).astype(np.float32)

    if shard_candidates is None:
        shard_candidates = tuple(
            s for s in (n // 4, n // 8, n // 16)
            if s >= 128 and s % 128 == 0 and n % s == 0
        ) or (max(128, (n // 8) // 128 * 128 or 128),)

    timings: dict[str, float] = {}
    best: PipelineKnobs | None = None
    best_t = float("inf")
    geom = None  # planner-visible (padded_rows, padded_dim) of the winner

    for rows_per_shard in shard_candidates:
        for rescore in rescore_candidates:
            store = DatasetStore.from_array(x, rows_per_shard=rows_per_shard,
                                            directory=directory)
            eng = ExactKNN(k=k, metric=metric, device_budget_bytes=1,
                           rescore_factor=rescore).fit_store(store)
            eng.enable_int8()
            meta = eng.dataset_meta(tier="int8")
            for prefetch in prefetch_candidates:
                for trigger in trigger_candidates:
                    req = SearchRequest(queries=q, tier="int8",
                                        prefetch_depth=prefetch,
                                        spec_trigger=trigger)
                    eng.search(req)  # warm compile + stream
                    samples = []
                    for _ in range(repeats):
                        t0 = time.perf_counter()
                        eng.search(req)
                        samples.append(time.perf_counter() - t0)
                    samples.sort()
                    t = samples[len(samples) // 2]
                    label = (f"shard{rows_per_shard}|pf{prefetch}"
                             f"|tr{trigger}|r{rescore}")
                    timings[label] = t
                    if t < best_t:
                        best_t = t
                        best = PipelineKnobs(prefetch, trigger, rescore,
                                             rows_per_shard)
                        geom = (meta.padded_rows, meta.padded_dim)

    assert best is not None and geom is not None
    for i, executor in enumerate(("fqsd-int8-streamed",
                                  "fqsd-int8-mmap-streamed")):
        cache.put_pipeline(
            pipeline_key(executor, m, geom[0], geom[1], "float32", metric, k),
            best, us_per_call=best_t * 1e6, n_candidates=len(timings),
            mirrored=bool(i),
        )
    return best, timings
