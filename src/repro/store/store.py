"""DatasetStore — every dataset representation behind one interface.

The paper's FQ-SD mode exists because the dataset outgrows device memory
(section 3.3 streams partitions over PCIe), and its section 5 names
quantization as the throughput lever: both are *storage* decisions
(bytes/element, placement, prefetch), so this layer owns them and the
planner reads them (:class:`repro.core.planner.DatasetStoreMeta`).

One store = a **manifest** of equal-geometry shards, each materialized in
up to two dtype tiers:

* ``f32``  — exact base tier: padded float32 vectors + row norms (+inf on
             padding/tombstones, the mask channel every executor honors);
* ``int8`` — 1 B/element scan tier (``repro.core.quantized``): symmetric
             per-row int8 codes + scales + a certified per-row error bound
             + the exact quantized norm, enabling the exact-with-rescore
             quantized executors — resident (fqsd-int8[-pallas]) and
             streamed (fqsd-int8[-mmap]-streamed, which scan codes shard
             by shard and rescore only candidate rows of the f32 tier).

Shards live either in host memory or as ``np.memmap``-backed files in a
directory (written with a JSON manifest; reopen with :meth:`open`).  Every
shard shares one padded shape, so streamed scans reuse one compiled step —
the fixed-bitstream invariant.

**Online mutation** is an append-only delta plus a tombstone mask:

* :meth:`upsert` appends rows to delta shards (fixed geometry, compiled
  once) and returns their external ids (never reused);
* :meth:`delete` flips a tombstone, which surfaces as a +inf norm — pure
  runtime data, so mutations never change compiled shapes ("no
  reflashing" holds under live traffic).

Results stay exact throughout: a query sees main shards minus tombstones
plus live delta rows.

**Crash-safe lifecycle** (directory-backed stores):

* every upsert/delete is logged to a CRC-framed write-ahead journal
  (:mod:`repro.store.journal`) and fsync'd *before* it is applied or
  acknowledged, so :meth:`open` after a crash at any point replays acked
  mutations and discards torn tails — never a half-visible mutation;
* :meth:`compact` folds delta rows + tombstones back into a fresh
  immutable shard **generation** (``gen_<k>/`` directory with its own
  manifest, re-quantizing the int8 tier so streamed scans return to
  1 B/element), then switches readers with a single atomic root-level
  ``CURRENT`` pointer update — atomic by pointer, no data rename, safe on
  failure. In-flight searches pin the generation they started on via
  refcounts (:meth:`snapshot` → :class:`StoreView`) and keep scanning it;
  old generations are garbage-collected only when unpinned. Geometry
  (rows_per_shard, padded_dim) is preserved across generations, so every
  compiled streamed step survives the swap — zero recompiles.

External ids survive compaction: a generation carries an optional per-row
id table (``rowids.npy``), identity for every freshly built store. Rows'
*positions* within a generation are internal; :class:`StoreView`
translates both directions (``external_ids`` / ``positional_mask``).
"""
from __future__ import annotations

import dataclasses
import math
import os
import shutil
import threading
import time
import zlib
from typing import Iterator, NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.partition import LANE, PaddedDataset, round_up
from repro.core.planner import DatasetStoreMeta
from repro.core.quantized import Int8Partition
from repro.faults import ShardCorruptError
from repro.store.journal import (
    JOURNAL_NAME,
    Journal,
    decode_upsert,
    encode_delete,
    encode_upsert,
)
from repro.store.manifest import (
    CURRENT_NAME,
    MANIFEST_NAME,
    Manifest,
    ShardMeta,
    crc32_of,
    crc32_of_arrays,
    read_current,
    write_current,
)

F32_TIER = "f32"
INT8_TIER = "int8"

#: Default cap on delta-shard geometry: small enough that the first upsert
#: on a huge store does not allocate a main-sized buffer, aligned so the
#: delta step executable is compiled once per store.
DELTA_ROWS_DEFAULT = 4096

#: manifest files/checksums key for the per-row CRC sidecar of the f32
#: tier (uint32 per padded row) — what lets gather_rows verify candidate
#: rows without re-hashing the whole shard.
ROWCRC_KEY = "f32_rowcrc"

#: per-generation external-id table file (int64 per main row); absent /
#: "" in the manifest means identity (position == id).
ROW_IDS_NAME = "rowids.npy"

GEN_DIR_FMT = "gen_{:06d}"


class Int8Shard(NamedTuple):
    """Host-side int8 tier of one shard (see repro.core.quantized).

    For disk-backed stores ``q`` is a read-only ``np.memmap`` of the raw
    codes file — a streamed quantized scan touches 1 B/element of disk
    plus the small per-row f32 channels, never the f32 tier."""

    q: np.ndarray  # (padded_rows, padded_dim) int8; ndarray or memmap
    scales: np.ndarray  # (padded_rows,) f32
    err: np.ndarray  # (padded_rows,) f32 — certified ||e_x|| upper bound
    norms_sq: np.ndarray  # (padded_rows,) f32 — exact norms; +inf on invalid
    qnorm_sq: np.ndarray  # (padded_rows,) f32 — EXACT ||x_hat||^2 (bound
    #                       soundness requires this exact value; persisted,
    #                       not re-derived, so reopening never reads f32)


class _Shard(NamedTuple):
    vectors: np.ndarray  # (padded_rows, padded_dim) f32; ndarray or memmap
    norms: np.ndarray  # (padded_rows,) f32; +inf beyond n_valid
    meta: ShardMeta
    rowcrc: np.ndarray | None = None  # (padded_rows,) uint32 per-row CRC


class _ShardSource:
    """Restartable view over one store tier: ``iter()`` opens a fresh
    :meth:`DatasetStore.iter_shards` pass (what DoubleBufferedStream needs
    to support multi-pass re-iteration of multi-array streams)."""

    def __init__(self, store, tier: str):
        self._store = store
        self._tier = tier

    def __iter__(self):
        return self._store.iter_shards(self._tier)


def _pad_block(rows: np.ndarray, padded_rows: int, padded_dim: int) -> np.ndarray:
    out = np.zeros((padded_rows, padded_dim), dtype=np.float32)
    out[: rows.shape[0], : rows.shape[1]] = rows
    return out


def _block_norms(block: np.ndarray, n_valid: int) -> np.ndarray:
    # the same reduction partition.make_padded uses, so resident and
    # streamed scans see bitwise-identical norms for identical rows
    norms = np.array(jnp.sum(jnp.asarray(block) ** 2, axis=-1))
    if not np.isfinite(norms[:n_valid]).all():
        # +inf is the tombstone sentinel every executor masks on — a row
        # whose norm overflows f32 would be ingested yet never returnable
        raise ValueError(
            "rows with non-finite f32 squared norms cannot be stored "
            "(values this large would be silently unreturnable)"
        )
    norms[n_valid:] = np.inf
    return norms


def _f32_name(i: int) -> str:
    return f"shard_{i:05d}.f32.bin"


def _norms_name(i: int) -> str:
    return f"shard_{i:05d}.norms.npy"


def _rowcrc_name(i: int) -> str:
    return f"shard_{i:05d}.rowcrc.npy"


def _int8_codes_name(i: int) -> str:
    return f"shard_{i:05d}.int8.bin"


def _int8_meta_name(i: int) -> str:
    return f"shard_{i:05d}.int8.npz"


def _row_crcs(block: np.ndarray) -> np.ndarray:
    """uint32 CRC32 per padded row of a contiguous f32 block."""
    b = np.ascontiguousarray(block, dtype=np.float32)
    return np.asarray([zlib.crc32(r.tobytes()) & 0xFFFFFFFF for r in b],
                      dtype=np.uint32)


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _try_remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


#: npz member order of the int8 meta file — ALSO the checksum order
#: (crc32_of_arrays runs over the arrays in this sequence).
_INT8_META_FIELDS = ("scales", "err", "norms_sq", "qnorm_sq")
INT8_META = "int8_meta"  # manifest files/checksums key for the meta npz


def _materialize_shards(v: np.ndarray, rows: int, padded_dim: int,
                        directory: str | None,
                        durable: bool = False):
    """Build the f32 tier of one generation from (n, d) rows: equal-geometry
    shards with norms and (when directory-backed) memmap files + per-row CRC
    sidecars. ``durable=True`` fsyncs every written file (the compaction
    path, where the files must be on stable storage before the pointer
    swap acknowledges them)."""
    n = v.shape[0]
    n_shards = max(1, math.ceil(n / rows))
    shards: list[_Shard] = []
    metas: list[ShardMeta] = []
    for i in range(n_shards):
        start = i * rows
        nv = min(rows, max(0, n - start))
        block = _pad_block(v[start: start + nv], rows, padded_dim)
        norms = _block_norms(block, nv)
        rowcrc = None
        files, sums = {}, {}
        if directory is not None:
            files = {F32_TIER: _f32_name(i), "f32_norms": _norms_name(i),
                     ROWCRC_KEY: _rowcrc_name(i)}
            sums = {F32_TIER: crc32_of(block)}
            mm = np.memmap(os.path.join(directory, files[F32_TIER]),
                           dtype=np.float32, mode="w+", shape=block.shape)
            mm[:] = block
            mm.flush()
            np.save(os.path.join(directory, files["f32_norms"]), norms)
            rowcrc = _row_crcs(block)
            np.save(os.path.join(directory, files[ROWCRC_KEY]), rowcrc)
            sums[ROWCRC_KEY] = crc32_of(rowcrc)
            if durable:
                for fname in files.values():
                    _fsync_file(os.path.join(directory, fname))
            # reopen read-only: the store never holds shard data in RAM
            block = np.memmap(os.path.join(directory, files[F32_TIER]),
                              dtype=np.float32, mode="r", shape=block.shape)
        meta = ShardMeta(shard_id=i, row_start=start, n_valid=nv,
                         padded_rows=rows, padded_dim=padded_dim,
                         files=files, checksums=sums)
        metas.append(meta)
        shards.append(_Shard(block, norms, meta, rowcrc))
    return shards, metas


class _Generation:
    """One immutable shard set plus the mutable delta that rides on it.

    ALL per-epoch state lives here (shards, tiers, tombstones, delta rows,
    id table), so the compactor's reader swap is a single reference
    assignment ``store._gen = new_gen`` — atomic under the GIL, and
    in-flight searches that pinned the old object keep a fully consistent
    view until they unpin."""

    __slots__ = ("number", "manifest", "shards", "int8", "directory",
                 "row_ids", "identity", "delta", "delta_tomb", "delta_full",
                 "delta_ids", "main_tomb", "dead_main", "dead_delta",
                 "refs", "obsolete", "collected", "lut")

    def __init__(self, number: int, manifest: Manifest, shards: list[_Shard],
                 directory: str | None = None,
                 row_ids: np.ndarray | None = None):
        self.number = number
        self.manifest = manifest
        self.shards = shards
        self.int8: list[Int8Shard] | None = None
        self.directory = directory
        self.row_ids = row_ids  # (n_main,) int64 or None = identity
        self.identity = row_ids is None
        self.delta: list[np.ndarray] = []  # appended rows, padded_dim wide
        self.delta_tomb: list[bool] = []
        # materialized FULL delta shards (rows immutable once a shard
        # fills): (block, base norms) pairs, so u upserts cost O(u)
        self.delta_full: list[tuple[np.ndarray, np.ndarray]] = []
        self.delta_ids: list[int] = []  # external id per delta row
        self.main_tomb = np.zeros(manifest.n_valid, dtype=bool)
        self.dead_main = 0
        self.dead_delta = 0
        self.refs = 0  # pinned readers (StoreView / iter_shards passes)
        self.obsolete = False  # superseded by a newer generation
        self.collected = False
        self.lut = None  # lazy external id -> position table

    @property
    def n_main(self) -> int:
        return self.manifest.n_valid

    @property
    def n_delta(self) -> int:
        return len(self.delta)


class StoreView:
    """A pinned, read-only view of ONE store generation.

    Holding a view guarantees the generation's shards, tombstones seen so
    far, and id tables stay valid (not garbage-collected) until
    :meth:`release` — what lets a streamed search keep scanning while the
    compactor swaps generations underneath it. Exposes the full read
    surface executors use (``read_shard`` / ``iter_shards`` /
    ``shard_source`` / ``delta_shards`` / ``gather_rows``), all positional
    within this generation, plus the two id translations the engine needs
    at the boundary: ``positional_mask`` (external mask in) and
    ``external_ids`` (positional results out)."""

    def __init__(self, store: "DatasetStore", gen: _Generation):
        self._store = store
        self._gen = gen
        self._released = False

    # -- lifecycle ---------------------------------------------------------
    def release(self) -> None:
        if not self._released:
            self._released = True
            self._store._unpin(self._gen)

    def __enter__(self) -> "StoreView":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    # -- geometry / identity ----------------------------------------------
    @property
    def generation(self) -> int:
        return self._gen.number

    @property
    def identity(self) -> bool:
        """True when position == external id for every row (no translation
        needed) — holds for every store that has never compacted away a
        deleted row."""
        return self._gen.identity

    @property
    def dim(self) -> int:
        return self._gen.manifest.dim

    @property
    def padded_dim(self) -> int:
        return self._gen.manifest.padded_dim

    @property
    def rows_per_shard(self) -> int:
        return self._gen.manifest.rows_per_shard

    @property
    def n_shards(self) -> int:
        return len(self._gen.shards)

    @property
    def n_main(self) -> int:
        return self._gen.n_main

    @property
    def n_delta(self) -> int:
        return self._gen.n_delta

    @property
    def is_mmap(self) -> bool:
        return self._store.is_mmap

    def meta(self, device_resident: bool, tier: str = F32_TIER,
             sharded: bool = False) -> DatasetStoreMeta:
        m = self._gen.manifest
        return DatasetStoreMeta(
            padded_rows=m.padded_rows_total,
            padded_dim=m.padded_dim,
            n_valid=m.n_valid,
            sharded=sharded,
            resident=device_resident,
            tier=tier,
            n_shards=len(self._gen.shards),
            rows_per_shard=m.rows_per_shard,
            mmap=self._store.is_mmap,
        )

    # -- reads (all positional within this generation) ---------------------
    def read_shard(self, i: int, tier: str = F32_TIER):
        return self._store._read_shard_of(self._gen, i, tier)

    def delta_shards(self) -> list[PaddedDataset]:
        return self._store._delta_shards_of(self._gen)

    def gather_rows(self, ids) -> np.ndarray:
        return self._store._gather_rows_of(self._gen, ids)

    def iter_shards(self, tier: str = F32_TIER) -> Iterator:
        g = self._gen
        if tier == F32_TIER:
            def gen():
                for i in range(len(g.shards)):
                    yield self.read_shard(i, F32_TIER)
                yield from self.delta_shards()

            return gen()
        if tier != INT8_TIER:
            raise ValueError(
                f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        if g.int8 is None:
            raise RuntimeError(
                "int8 tier not materialized; call ensure_tier('int8')")

        def gen8():
            for i in range(len(g.shards)):
                yield self.read_shard(i, INT8_TIER)

        return gen8()

    def shard_source(self, tier: str = F32_TIER) -> _ShardSource:
        if tier not in (F32_TIER, INT8_TIER):
            raise ValueError(
                f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        return _ShardSource(self, tier)

    def __iter__(self) -> Iterator[PaddedDataset]:
        return self.iter_shards()

    # -- id translation ----------------------------------------------------
    def external_ids(self, idx) -> np.ndarray:
        """Map positional result indices of this generation to external ids
        (-1 stays -1; padding positions map to -1)."""
        idx = np.asarray(idx, dtype=np.int64)
        g = self._gen
        if g.identity:
            return idx
        out = np.full(idx.shape, -1, dtype=np.int64)
        rid = (g.row_ids if g.row_ids is not None
               else np.arange(g.n_main, dtype=np.int64))
        main = (idx >= 0) & (idx < g.n_main)
        out[main] = rid[idx[main]]
        nd = len(g.delta_ids)
        if nd:
            did = np.asarray(g.delta_ids, dtype=np.int64)
            d = (idx >= g.n_main) & (idx < g.n_main + nd)
            out[d] = did[idx[d] - g.n_main]
        return out

    def positional_mask(self, mask: np.ndarray) -> np.ndarray:
        """Convert an external-id-indexed boolean mask (length >= n_ids)
        into this generation's positional layout (main rows then delta
        rows). Ids compacted away simply have no position."""
        g = self._gen
        mask = np.asarray(mask, dtype=bool).reshape(-1)
        if g.identity:
            n_pos = g.n_main + g.n_delta
            return mask[:n_pos] if mask.shape[0] > n_pos else mask
        rid = (g.row_ids if g.row_ids is not None
               else np.arange(g.n_main, dtype=np.int64))
        nd = len(g.delta_ids)
        out = np.zeros(g.n_main + nd, dtype=bool)
        out[: g.n_main] = mask[rid]
        if nd:
            out[g.n_main:] = mask[np.asarray(g.delta_ids, dtype=np.int64)]
        return out


class DatasetStore:
    """Tiered, shard-manifested dataset with online upsert/delete, a
    crash-safe journaled mutation path, and background compaction.

    Construct with :meth:`from_array` (optionally writing mmap shards to a
    directory) or :meth:`open` (reopen a written directory out-of-core,
    replaying any journaled mutations).
    """

    def __init__(self, manifest: Manifest, shards: list[_Shard],
                 directory: str | None = None,
                 delta_rows: int = DELTA_ROWS_DEFAULT):
        self._directory = directory
        self._gen = _Generation(manifest.generation, manifest, shards,
                                directory=directory)
        self._delta_rows_cap = round_up(
            min(delta_rows, manifest.rows_per_shard), LANE
        )
        #: external-id allocation counter; ids are never reused, so this
        #: only grows (persisted in the manifest at compaction time and
        #: re-advanced by journal replay)
        self._next_id = (manifest.next_id if manifest.next_id >= 0
                         else manifest.n_valid)
        self._mutations = 0  # version counter; device views sync on change
        self._lock = threading.RLock()
        self._journal: Journal | None = None
        self._retired: list[_Generation] = []  # obsolete but still pinned
        self._compact_state = {"running": False, "compactions": 0,
                               "last": None, "error": None}
        #: when set, a mutation that leaves >= this many pending delta rows
        #: + tombstones kicks off a background compaction (serve knob)
        self.auto_compact_pending: int | None = None
        #: optional per-store fault injector (repro.faults.FaultInjector);
        #: when None the process-wide one (repro.faults.install) applies
        self.fault_injector = None
        #: re-check shard CRCs on every read_shard / per-row CRCs on every
        #: gather_rows (costs an extra pass over the bytes read)
        self.verify_on_read = False

    # ------------------------------------------------------------- factories
    @classmethod
    def from_array(
        cls,
        vectors,
        rows_per_shard: int | None = None,
        directory: str | None = None,
        row_mult: int = LANE,
        dim_mult: int = LANE,
        tiers: Sequence[str] = (F32_TIER,),
        delta_rows: int = DELTA_ROWS_DEFAULT,
    ) -> "DatasetStore":
        """Build a store from an (N, d) array.

        ``rows_per_shard=None`` builds one shard padded to ``row_mult`` (the
        resident fast path); otherwise equal shards of the given (aligned)
        size. With ``directory`` the f32 tier is written as raw memmap files
        plus ``manifest.json`` (+ per-row CRC sidecars, the ``CURRENT``
        generation pointer, and an empty journal) and the returned store
        reads through memmaps.
        """
        v = np.asarray(vectors, dtype=np.float32)
        if v.ndim != 2:
            raise ValueError(f"expected (N, d) dataset, got {v.shape}")
        n, d = v.shape
        padded_dim = round_up(d, dim_mult)
        if rows_per_shard is None:
            rows = round_up(max(n, 1), row_mult)
        else:
            rows = round_up(max(rows_per_shard, 1), row_mult)

        if directory is not None:
            os.makedirs(directory, exist_ok=True)

        shards, metas = _materialize_shards(v, rows, padded_dim, directory)
        manifest = Manifest(dim=d, padded_dim=padded_dim, rows_per_shard=rows,
                            n_valid=n, tiers=(F32_TIER,), shards=tuple(metas),
                            generation=0, next_id=n)
        store = cls(manifest, shards, directory=directory, delta_rows=delta_rows)
        if directory is not None:
            manifest.save(directory)
            # generation 0 lives at the store root ("."): readers that
            # predate generations still find manifest.json where it was
            write_current(directory, ".")
            store._attach_journal(directory)
        for t in tiers:
            if t != F32_TIER:
                store.ensure_tier(t)
        return store

    @classmethod
    def open(cls, directory: str, verify: bool = False,
             delta_rows: int = DELTA_ROWS_DEFAULT,
             verify_on_read: bool = False) -> "DatasetStore":
        """Reopen a written store; shard vectors stay on disk (np.memmap).

        Recovery protocol, in order: (1) resolve the live generation via
        the root ``CURRENT`` pointer (missing = legacy root layout);
        (2) structurally validate its manifest (:class:`ManifestError`
        names the offending field); (3) sweep orphan generation
        directories and superseded root-generation files left by a crashed
        compaction (the pointer is the commit point — anything it does not
        name is garbage); (4) replay the generation's journal, truncating
        any torn tail. Every crash point therefore reopens to a state
        bit-identical to "before" or "after" the interrupted operation.

        ``verify=True`` recomputes every f32 checksum (reads all shards —
        use in tests and integrity audits, not on the serving path).
        ``verify_on_read=True`` arms per-read CRC checking on the serving
        path instead: every :meth:`read_shard` re-hashes the shard's bytes
        against the manifest, and every :meth:`gather_rows` re-hashes the
        candidate rows it returns, turning silent corruption into a loud
        :class:`~repro.faults.ShardCorruptError`.
        """
        cur = read_current(directory)
        gen_name = cur if cur is not None else "."
        gen_dir = (directory if gen_name == "."
                   else os.path.join(directory, gen_name))
        manifest = Manifest.load(gen_dir).validate()
        shards: list[_Shard] = []
        for m in manifest.shards:
            vec = np.memmap(os.path.join(gen_dir, m.files[F32_TIER]),
                            dtype=np.float32, mode="r",
                            shape=(m.padded_rows, m.padded_dim))
            norms = np.load(os.path.join(gen_dir, m.files["f32_norms"]))
            rowcrc = None
            if ROWCRC_KEY in m.files:
                rowcrc = np.load(os.path.join(gen_dir, m.files[ROWCRC_KEY]))
            if verify and crc32_of(vec) != m.checksums[F32_TIER]:
                raise ValueError(
                    f"checksum mismatch on shard {m.shard_id} "
                    f"({m.files[F32_TIER]}): file corrupt or truncated"
                )
            if (verify and rowcrc is not None
                    and ROWCRC_KEY in m.checksums
                    and crc32_of(rowcrc) != m.checksums[ROWCRC_KEY]):
                raise ValueError(
                    f"checksum mismatch on row-CRC sidecar of shard "
                    f"{m.shard_id} ({m.files[ROWCRC_KEY]}): file corrupt "
                    f"or truncated"
                )
            shards.append(_Shard(vec, norms, m, rowcrc))
        store = cls(manifest, shards, directory=directory, delta_rows=delta_rows)
        store._gen.directory = gen_dir
        if manifest.row_ids_file:
            row_ids = np.asarray(
                np.load(os.path.join(gen_dir, manifest.row_ids_file)),
                dtype=np.int64)
            store._gen.row_ids = row_ids
            store._gen.identity = bool(
                np.array_equal(row_ids, np.arange(row_ids.shape[0])))
        store.verify_on_read = bool(verify_on_read)
        if INT8_TIER in manifest.tiers:
            store._gen.int8 = [cls._load_int8_shard(gen_dir, m, verify)
                               for m in manifest.shards]
        store._sweep_stale(gen_name)
        store._attach_journal(gen_dir)
        store._replay_journal()
        return store

    def _attach_journal(self, gen_dir: str) -> None:
        self._journal = Journal(os.path.join(gen_dir, JOURNAL_NAME),
                                self._active_injector)

    def _replay_journal(self) -> int:
        """Apply acked-but-uncompacted mutations from the generation's
        journal (truncating any torn tail — see Journal.replay). Records
        re-apply through the same in-memory paths mutations use, minus the
        journaling, so a replayed store is bit-identical to one that never
        crashed."""
        assert self._journal is not None
        n = 0
        for rec in self._journal.replay():
            op = rec.get("op")
            if op == "upsert":
                id0, v = decode_upsert(rec)
                padded = np.zeros((v.shape[0], self.padded_dim),
                                  dtype=np.float32)
                padded[:, : self.dim] = v
                ids = np.arange(id0, id0 + v.shape[0], dtype=np.int64)
                self._apply_upsert_gen(self._gen, padded, ids)
            elif op == "delete":
                pos = self._resolve_delete_locked(
                    [int(g) for g in rec["ids"]])
                self._tombstone_gen(self._gen, pos)
            else:  # unknown op from a future version: fail loud, not quiet
                raise ValueError(f"unknown journal record op {op!r}")
            self._mutations += 1
            n += 1
        return n

    def _sweep_stale(self, gen_name: str) -> None:
        """Remove what a crashed compaction may have left behind: orphan
        generation directories the CURRENT pointer does not name, tmp
        pointer/manifest files, and — once the pointer has moved off the
        root — generation 0's superseded shard files."""
        root = self._directory
        if root is None:
            return
        _try_remove(os.path.join(root, CURRENT_NAME + ".tmp"))
        for name in sorted(os.listdir(root)):
            if (name.startswith("gen_") and name != gen_name
                    and os.path.isdir(os.path.join(root, name))):
                shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        if gen_name == ".":
            return
        # the live generation is a subdirectory; any root-level manifest +
        # shard files are the dead generation 0 (crash between pointer
        # swap and GC)
        root_manifest = os.path.join(root, MANIFEST_NAME)
        if os.path.exists(root_manifest):
            try:
                old = Manifest.load(root)
            except Exception:
                old = None
            if old is not None:
                for m in old.shards:
                    for fname in m.files.values():
                        _try_remove(os.path.join(root, fname))
                if old.row_ids_file:
                    _try_remove(os.path.join(root, old.row_ids_file))
            _try_remove(root_manifest)
        _try_remove(os.path.join(root, MANIFEST_NAME + ".tmp"))
        _try_remove(os.path.join(root, JOURNAL_NAME))

    @staticmethod
    def _load_int8_shard(directory: str, m: ShardMeta,
                         verify: bool) -> Int8Shard:
        """Open one shard's persisted int8 tier: codes as a read-only memmap
        plus the per-row meta npz (scales/err/norms/qnorm). Never touches
        the f32 tier. ``verify=True`` recomputes both CRCs; an unreadable
        meta file is reported as corruption either way.

        Legacy stores (format written before the codes/meta split) carry a
        single ``.int8.npz`` holding the codes too — loaded into host RAM,
        with the exact quantized norm re-derived from codes + scales (the
        same formula quantize time uses, so bounds agree bitwise)."""
        codes_file = m.files[INT8_TIER]
        legacy = codes_file.endswith(".npz")
        meta_file = codes_file if legacy else m.files[INT8_META]
        try:
            with np.load(os.path.join(directory, meta_file)) as z:
                meta = {name: z[name] for name in z.files}
        except Exception as e:
            raise ValueError(
                f"int8 meta of shard {m.shard_id} ({meta_file}) is "
                f"unreadable: file corrupt or truncated ({e})"
            ) from e
        if legacy:
            from repro.core.quantized import quantized_norm_sq

            codes = meta.pop("q")
            if "qnorm_sq" not in meta:
                meta["qnorm_sq"] = np.asarray(
                    quantized_norm_sq(codes, meta["scales"]))
            if verify and crc32_of(codes) != m.checksums[INT8_TIER]:
                raise ValueError(
                    f"checksum mismatch on int8 codes of shard {m.shard_id} "
                    f"({codes_file}): file corrupt or truncated"
                )
            return Int8Shard(codes, **meta)
        codes = np.memmap(os.path.join(directory, codes_file),
                          dtype=np.int8, mode="r",
                          shape=(m.padded_rows, m.padded_dim))
        if verify:
            if crc32_of(codes) != m.checksums[INT8_TIER]:
                raise ValueError(
                    f"checksum mismatch on int8 codes of shard {m.shard_id} "
                    f"({codes_file}): file corrupt or truncated"
                )
            got = crc32_of_arrays(*(meta[f] for f in _INT8_META_FIELDS))
            if got != m.checksums[INT8_META]:
                raise ValueError(
                    f"checksum mismatch on int8 meta of shard {m.shard_id} "
                    f"({meta_file}): file corrupt or truncated"
                )
        return Int8Shard(codes, **meta)

    # ---------------------------------------------- generation-delegating
    @property
    def manifest(self) -> Manifest:
        return self._gen.manifest

    @manifest.setter
    def manifest(self, value: Manifest) -> None:
        self._gen.manifest = value

    @property
    def _shards(self) -> list[_Shard]:
        return self._gen.shards

    @property
    def _int8(self) -> list[Int8Shard] | None:
        return self._gen.int8

    @property
    def generation(self) -> int:
        """Number of the live generation (bumped by every compaction) —
        engines watch this alongside :attr:`mutation_count` to know when a
        full view rebuild (vs an in-place norms refresh) is needed."""
        return self._gen.number

    # ------------------------------------------------------------ geometry
    @property
    def dim(self) -> int:
        return self.manifest.dim

    @property
    def padded_dim(self) -> int:
        return self.manifest.padded_dim

    @property
    def rows_per_shard(self) -> int:
        return self.manifest.rows_per_shard

    @property
    def n_shards(self) -> int:
        return self.manifest.n_shards

    @property
    def n_main(self) -> int:
        """Rows in the main (manifested) shards, tombstoned or not."""
        return self.manifest.n_valid

    @property
    def n_delta(self) -> int:
        return self._gen.n_delta

    @property
    def n_ids(self) -> int:
        """Size of the external id space (ids ever allocated; never shrinks
        — compaction reclaims rows, not ids)."""
        return self._next_id

    @property
    def n_live(self) -> int:
        """Rows a query must see: main + delta, minus tombstones."""
        g = self._gen
        return g.n_main + g.n_delta - g.dead_main - g.dead_delta

    @property
    def is_mmap(self) -> bool:
        return self._directory is not None

    @property
    def directory(self) -> str | None:
        return self._directory

    @property
    def tiers(self) -> tuple:
        return self.manifest.tiers if self._gen.int8 is None else tuple(
            dict.fromkeys((*self.manifest.tiers, INT8_TIER))
        )

    @property
    def mutation_count(self) -> int:
        """Bumped on every upsert/delete (and once per generation swap);
        device views resync when it moves."""
        return self._mutations

    def nbytes(self, tier: str = F32_TIER) -> int:
        """Scan bytes of one full pass over the main shards at `tier`."""
        per_elem = 4 if tier == F32_TIER else 1
        return self.n_shards * self.rows_per_shard * self.padded_dim * per_elem

    def meta(self, device_resident: bool, tier: str = F32_TIER,
             sharded: bool = False) -> DatasetStoreMeta:
        """Planner-visible facts: geometry + tier + residency + shard count."""
        return DatasetStoreMeta(
            padded_rows=self.manifest.padded_rows_total,
            padded_dim=self.padded_dim,
            n_valid=self.n_main,
            sharded=sharded,
            resident=device_resident,
            tier=tier,
            n_shards=self.n_shards,
            rows_per_shard=self.rows_per_shard,
            mmap=self.is_mmap,
        )

    # ------------------------------------------------------------- mutation
    def upsert(self, vectors) -> np.ndarray:
        """Append rows; returns their external ids (ids are never reused).

        Durability: on directory-backed stores the rows are framed into the
        write-ahead journal and fsync'd BEFORE they are applied or
        acknowledged — a crash after return cannot lose them, a crash
        before return cannot half-apply them. Appended rows live in
        fixed-geometry delta shards until :meth:`compact` folds them into
        the next generation; queries see them immediately and exactly.
        """
        v = np.asarray(vectors, dtype=np.float32)
        if v.ndim == 1:
            v = v[None, :]
        if v.ndim != 2 or v.shape[1] != self.dim:
            raise ValueError(
                f"upsert expects (m, {self.dim}) vectors, got {v.shape}"
            )
        padded = np.zeros((v.shape[0], self.padded_dim), dtype=np.float32)
        padded[:, : self.dim] = v
        _block_norms(padded, v.shape[0])  # reject unreturnable rows up front
        with self._lock:
            ids = np.arange(self._next_id, self._next_id + v.shape[0],
                            dtype=np.int64)
            if self._journal is not None:
                self._journal.append(encode_upsert(int(ids[0]), v))
            self._apply_upsert_gen(self._gen, padded, ids)
            self._mutations += 1
            self._maybe_auto_compact_locked()
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows by external id. Exact immediately: a tombstone is
        a +inf norm, so the row can never enter a kNN queue — no shape
        changes, no recompilation, no rewrite of shard files. Journaled
        (fsync before apply/ack) like :meth:`upsert`.

        Atomic: every id is validated before any tombstone flips (or any
        journal record lands), so a bad id leaves the store (and attached
        engine views) untouched.
        """
        gids = [int(g) for g in np.atleast_1d(np.asarray(ids, dtype=np.int64))]
        with self._lock:
            pos = self._resolve_delete_locked(gids)
            if self._journal is not None:
                self._journal.append(encode_delete(gids))
            self._tombstone_gen(self._gen, pos)
            self._mutations += 1
            self._maybe_auto_compact_locked()

    def _apply_upsert_gen(self, g: _Generation, padded: np.ndarray,
                          ids: np.ndarray) -> None:
        if g.identity and int(ids[0]) != g.n_main + g.n_delta:
            g.identity = False
        g.delta.extend(padded)
        g.delta_tomb.extend([False] * len(ids))
        g.delta_ids.extend(int(x) for x in ids)
        g.lut = None
        self._next_id = max(self._next_id, int(ids[-1]) + 1)

    def _resolve_delete_locked(self, gids: list[int]) -> list[int]:
        """Validate external ids for deletion; returns their positions in
        the live generation. Raises KeyError (naming the first bad id)
        without touching any state."""
        g = self._gen
        lut = None if g.identity else self._lut_of(g)
        seen: set[int] = set()
        out: list[int] = []
        for gid in gids:
            if not 0 <= gid < self._next_id:
                raise KeyError(
                    f"row {gid} does not exist (n={self._next_id})"
                )
            p = gid if lut is None else int(lut[gid])
            if p < 0:
                raise KeyError(f"row {gid} already deleted")
            already = (g.main_tomb[p] if p < g.n_main
                       else g.delta_tomb[p - g.n_main])
            if already or gid in seen:
                raise KeyError(f"row {gid} already deleted")
            seen.add(gid)
            out.append(p)
        return out

    @staticmethod
    def _tombstone_gen(g: _Generation, positions: list[int]) -> None:
        for p in positions:
            if p < g.n_main:
                if not g.main_tomb[p]:
                    g.main_tomb[p] = True
                    g.dead_main += 1
            else:
                j = p - g.n_main
                if not g.delta_tomb[j]:
                    g.delta_tomb[j] = True
                    g.dead_delta += 1

    def _lut_of(self, g: _Generation) -> np.ndarray:
        """Lazy external id -> generation position table (-1 = id has no
        live-generation row: never allocated here, or compacted away)."""
        need = max(self._next_id, 1)
        if g.lut is None or g.lut.shape[0] < need:
            lut = np.full(need, -1, dtype=np.int64)
            if g.n_main:
                rid = (g.row_ids if g.row_ids is not None
                       else np.arange(g.n_main, dtype=np.int64))
                lut[rid] = np.arange(g.n_main, dtype=np.int64)
            if g.delta_ids:
                lut[np.asarray(g.delta_ids, dtype=np.int64)] = (
                    g.n_main + np.arange(len(g.delta_ids), dtype=np.int64))
            g.lut = lut
        return g.lut

    # ------------------------------------------------------------- int8 tier
    def ensure_tier(self, tier: str) -> None:
        """Materialize `tier` for every main shard (idempotent).

        The int8 tier is quantized from the padded f32 blocks with the
        certified per-row error bound of ``repro.core.quantized``; invalid
        rows (padding) carry +inf norms so the masked quantized scan can
        never admit them.
        """
        if tier == F32_TIER:
            return
        if tier != INT8_TIER:
            raise ValueError(f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        with self._lock:
            if self._gen.int8 is not None:
                return
            self._quantize_generation(self._gen)

    def _quantize_generation(self, g: _Generation) -> None:
        """Build (and for directory-backed generations, persist) the int8
        tier of every shard in `g`, updating its manifest in place."""
        from repro.core.quantized import quantize_rows

        shards: list[Int8Shard] = []
        metas: list[ShardMeta] = []
        for s in g.shards:
            qd = quantize_rows(s.vectors)
            norms = np.asarray(qd.norms_sq).copy()
            norms[s.meta.n_valid:] = np.inf
            i8 = Int8Shard(np.asarray(qd.q), np.asarray(qd.scales),
                           np.asarray(qd.err), norms, np.asarray(qd.qnorm_sq))
            m = s.meta
            if g.directory is not None:
                # codes as a raw memmap file (streamed at 1 B/element),
                # per-row f32 channels in a small npz side file; both CRC'd
                # in the manifest so open(verify=True) covers the tier
                codes_name = _int8_codes_name(m.shard_id)
                meta_name = _int8_meta_name(m.shard_id)
                mm = np.memmap(os.path.join(g.directory, codes_name),
                               dtype=np.int8, mode="w+", shape=i8.q.shape)
                mm[:] = i8.q
                mm.flush()
                np.savez(os.path.join(g.directory, meta_name),
                         **{f: getattr(i8, f) for f in _INT8_META_FIELDS})
                m = ShardMeta(
                    shard_id=m.shard_id, row_start=m.row_start,
                    n_valid=m.n_valid, padded_rows=m.padded_rows,
                    padded_dim=m.padded_dim,
                    files={**m.files, INT8_TIER: codes_name,
                           INT8_META: meta_name},
                    checksums={**m.checksums, INT8_TIER: crc32_of(i8.q),
                               INT8_META: crc32_of_arrays(
                                   *(getattr(i8, f)
                                     for f in _INT8_META_FIELDS))},
                )
                # reopen read-only: codes stream from disk, not from RAM
                codes = np.memmap(os.path.join(g.directory, codes_name),
                                  dtype=np.int8, mode="r", shape=i8.q.shape)
                i8 = i8._replace(q=codes)
            shards.append(i8)
            metas.append(m)
        g.int8 = shards
        tiers = tuple(dict.fromkeys((*g.manifest.tiers, INT8_TIER)))
        g.manifest = dataclasses.replace(
            g.manifest, tiers=tiers, shards=tuple(metas))
        if g.directory is not None:
            g.manifest.save(g.directory)
            g.shards = [
                _Shard(s.vectors, s.norms, m, s.rowcrc)
                for s, m in zip(g.shards, metas)
            ]

    def has_tier(self, tier: str) -> bool:
        return tier == F32_TIER or (
            tier == INT8_TIER and self._gen.int8 is not None)

    # ------------------------------------------------------------- read side
    def _shard_norms(self, i: int) -> np.ndarray:
        return self._shard_norms_of(self._gen, i)

    @staticmethod
    def _shard_norms_of(g: _Generation, i: int) -> np.ndarray:
        """Shard norms with the tombstone mask folded in (+inf on dead rows)."""
        s = g.shards[i]
        norms = np.array(s.norms, dtype=np.float32, copy=True)
        start, nv = s.meta.row_start, s.meta.n_valid
        dead = g.main_tomb[start: start + nv]
        if dead.any():
            norms[:nv][dead] = np.inf
        return norms

    def _active_injector(self):
        if self.fault_injector is not None:
            return self.fault_injector
        from repro.faults import active

        return active()

    def read_shard(self, i: int, tier: str = F32_TIER):
        """Read ONE main shard at `tier` — the unit of streamed resilience.

        Reads the LIVE generation; searches that must survive a concurrent
        compaction read through a pinned :meth:`snapshot` instead. Returns
        the same partition :meth:`iter_shards` would yield at position
        ``i`` (tombstones/validity folded in). This is where the fault
        hooks live (``fault_injector.on_shard_read`` / ``maybe_corrupt``)
        and where ``verify_on_read`` re-hashes the shard's bytes against
        the manifest CRCs, raising
        :class:`~repro.faults.ShardCorruptError` on mismatch — so a
        mid-scan bit flip surfaces as a typed, retryable error instead of
        a silently wrong top-k.
        """
        return self._read_shard_of(self._gen, i, tier)

    def _read_shard_of(self, g: _Generation, i: int, tier: str):
        if not 0 <= i < len(g.shards):
            raise IndexError(f"shard {i} out of range (n={len(g.shards)})")
        inj = self._active_injector()
        if inj is not None:
            inj.on_shard_read(i, tier)
        s = g.shards[i]
        if tier == F32_TIER:
            vec = s.vectors
            if inj is not None:
                vec = inj.maybe_corrupt(vec, i, tier)
            if self.verify_on_read:
                want = s.meta.checksums.get(F32_TIER)
                if want is not None and crc32_of(vec) != want:
                    raise ShardCorruptError(
                        f"CRC mismatch on f32 shard {i}: bytes changed "
                        f"since the manifest was written", i, tier)
            return PaddedDataset(vec, self._shard_norms_of(g, i),
                                 s.meta.n_valid, s.meta.row_start)
        if tier != INT8_TIER:
            raise ValueError(
                f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        if g.int8 is None:
            raise RuntimeError(
                "int8 tier not materialized; call ensure_tier('int8')")
        i8 = g.int8[i]
        codes = i8.q
        if inj is not None:
            codes = inj.maybe_corrupt(codes, i, tier)
        if self.verify_on_read:
            want = s.meta.checksums.get(INT8_TIER)
            if want is not None and crc32_of(codes) != want:
                raise ShardCorruptError(
                    f"CRC mismatch on int8 codes of shard {i}: bytes "
                    f"changed since the manifest was written", i, tier)
            want = s.meta.checksums.get(INT8_META)
            if want is not None and crc32_of_arrays(
                    *(getattr(i8, f) for f in _INT8_META_FIELDS)) != want:
                raise ShardCorruptError(
                    f"CRC mismatch on int8 meta of shard {i}: per-row "
                    f"channels changed since the manifest was written",
                    i, tier)
        norms = np.asarray(i8.norms_sq)
        start, nv = s.meta.row_start, s.meta.n_valid
        dead = g.main_tomb[start: start + nv]
        if dead.any():
            norms = norms.copy()
            norms[:nv][dead] = np.inf
        # validity (padding + tombstones) folds onto the exact quantized
        # norm — the one channel the scan step masks on
        qnorm = np.where(np.isfinite(norms), i8.qnorm_sq,
                         np.float32(np.inf)).astype(np.float32)
        return Int8Partition(codes, i8.scales, i8.err, qnorm, nv, start)

    def delta_shards(self) -> list[PaddedDataset]:
        """Live appended rows as fixed-geometry padded shards (host arrays).

        Every delta shard shares one shape, so the per-partition step
        executable is compiled once per store no matter how many upserts
        arrive. base_index continues the positional space after the main
        rows. Full shards are materialized once (rows are immutable after a
        shard fills; only the tombstone-masked norms are re-derived per
        call); the trailing partial shard is rebuilt until it fills.
        """
        return self._delta_shards_of(self._gen)

    def _delta_shards_of(self, g: _Generation) -> list[PaddedDataset]:
        if not g.delta:
            return []
        rows = self._delta_rows_cap
        n = len(g.delta)
        n_full = n // rows
        while len(g.delta_full) < n_full:
            i = len(g.delta_full)
            block = _pad_block(np.stack(g.delta[i * rows: (i + 1) * rows]),
                               rows, self.padded_dim)
            g.delta_full.append((block, _block_norms(block, rows)))
        tomb = np.asarray(g.delta_tomb, dtype=bool)
        out: list[PaddedDataset] = []
        for i in range(n_full):
            block, base_norms = g.delta_full[i]
            norms = base_norms.copy()
            dead = tomb[i * rows: (i + 1) * rows]
            if dead.any():
                norms[dead] = np.inf
            out.append(PaddedDataset(block, norms, rows, g.n_main + i * rows))
        tail = n - n_full * rows
        if tail:
            block = _pad_block(np.stack(g.delta[n_full * rows:]),
                               rows, self.padded_dim)
            norms = _block_norms(block, tail)
            dead = tomb[n_full * rows:]
            if dead.any():
                norms[:tail][dead] = np.inf
            out.append(PaddedDataset(block, norms, tail,
                                     g.n_main + n_full * rows))
        return out

    def iter_shards(self, tier: str = F32_TIER) -> Iterator:
        """Fresh host-side shard scan at `tier` (restartable: every call
        opens a new pass — safe to hand to DoubleBufferedStream).

        Each pass pins the generation it starts on (see :meth:`snapshot`),
        so a compaction swap mid-scan cannot pull shards out from under it
        — the pass finishes on the generation it began, and the pin is
        dropped when the iterator is exhausted or closed.

        ``tier="f32"`` yields :class:`PaddedDataset` over main + delta
        shards. ``tier="int8"`` yields the multi-array
        :class:`~repro.core.quantized.Int8Partition` (codes + scales + err
        + validity-folded exact quantized norm) over the MAIN shards only —
        delta rows have no quantized representation, so streamed int8
        consumers fold them exactly from :meth:`delta_shards` (the
        executors' rescore union does). The streaming layer device_puts
        each partition, which for mmap shards is the moment the bytes leave
        the disk (one sequential read per shard, double buffered against
        compute).
        """
        if tier not in (F32_TIER, INT8_TIER):
            raise ValueError(
                f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        if tier == INT8_TIER and self._gen.int8 is None:
            raise RuntimeError(
                "int8 tier not materialized; call ensure_tier('int8')")

        def gen():
            with self.snapshot() as view:
                yield from view.iter_shards(tier)

        return gen()

    def shard_source(self, tier: str = F32_TIER) -> "_ShardSource":
        """A restartable iterable over :meth:`iter_shards` at `tier` —
        every ``iter()`` opens a fresh pass, so it composes with
        DoubleBufferedStream re-iteration (multi-pass streamed scans)."""
        if tier not in (F32_TIER, INT8_TIER):
            raise ValueError(
                f"unknown tier {tier!r}; known: {F32_TIER}, {INT8_TIER}")
        return _ShardSource(self, tier)

    def gather_rows(self, ids) -> np.ndarray:
        """Random-access read of main-shard rows by generation position ->
        (len(ids), padded_dim) f32. The rescore path of the streamed int8
        executors: only *candidate* rows of the f32 tier are touched (for
        mmap stores, these are the random disk reads the certified scan
        buys down from a full 4 B/element pass). Negative ids (empty queue
        slots) and out-of-main ids yield zero rows — callers mask them by
        validity.

        Under ``verify_on_read=True`` every gathered row is re-hashed
        against the per-row CRC sidecar written at build/compaction time,
        so a flipped byte in a candidate row raises
        :class:`~repro.faults.ShardCorruptError` instead of skewing the
        rescored top-k.

        Thread-safety contract: this is a pure read (numpy/memmap slices,
        no store state mutated), safe to call from a background thread
        concurrently with ``iter_shards``/``shard_source`` iteration — the
        speculative overlapped gather (``core.streaming.SpeculativeGather``)
        relies on exactly that to hide the rescore's random reads under the
        int8 scan tail. Concurrent *mutation* (upsert/delete) is NOT part
        of the contract; the engine serializes searches and mutations."""
        return self._gather_rows_of(self._gen, ids)

    def _gather_rows_of(self, g: _Generation, ids) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        inj = self._active_injector()
        if inj is not None:
            inj.on_gather(int(ids.shape[0]))
        rows_per = self.rows_per_shard
        out = np.zeros((ids.shape[0], self.padded_dim), dtype=np.float32)
        ok = (ids >= 0) & (ids < len(g.shards) * rows_per)
        if ok.any():
            dest = np.flatnonzero(ok)
            sid = ids[dest] // rows_per
            row = ids[dest] % rows_per
            for s in np.unique(sid):
                sel = sid == s
                sh = g.shards[int(s)]
                rows_idx = row[sel]
                vals = sh.vectors[rows_idx]
                if self.verify_on_read and sh.rowcrc is not None:
                    for rpos, rv in zip(rows_idx, np.asarray(vals)):
                        got = zlib.crc32(
                            np.ascontiguousarray(rv).tobytes()) & 0xFFFFFFFF
                        if got != int(sh.rowcrc[rpos]):
                            raise ShardCorruptError(
                                f"per-row CRC mismatch on row {int(rpos)} of "
                                f"f32 shard {int(s)} (candidate gather): "
                                f"bytes changed since the shard was written",
                                int(s), F32_TIER)
                out[dest[sel]] = vals
        return out

    def __iter__(self) -> Iterator[PaddedDataset]:
        """A DatasetStore is a restartable shard source (each iter() is a
        fresh scan) — composes directly with DataPipeline / streaming."""
        return self.iter_shards()

    def resident(self) -> PaddedDataset:
        """Main shards concatenated into one host PaddedDataset (reads all
        shards — only call when the store fits the device budget).

        Valid rows occupy positions 0..n_main-1 (shards fill sequentially);
        positions equal external ids until the first id-remapping
        compaction (``StoreView.identity``), after which the engine
        translates result indices. Tombstones ride the norms channel.
        """
        g = self._gen
        if len(g.shards) == 1:
            vec = np.asarray(g.shards[0].vectors)
        else:
            vec = np.concatenate([np.asarray(s.vectors) for s in g.shards])
        norms = np.concatenate(
            [self._shard_norms_of(g, i) for i in range(len(g.shards))])
        return PaddedDataset(vec, norms, g.n_main, 0)

    def resident_norms(self) -> np.ndarray:
        """Norms of :meth:`resident` alone — the only channel mutations
        touch, so engines refresh this (same shape, no recompile)."""
        g = self._gen
        return np.concatenate(
            [self._shard_norms_of(g, i) for i in range(len(g.shards))])

    def int8_resident(self) -> Int8Shard:
        """Main shards' int8 tier concatenated (norms carry tombstones)."""
        g = self._gen
        if g.int8 is None:
            raise RuntimeError("int8 tier not materialized; call ensure_tier('int8')")
        cat = lambda field: np.concatenate([getattr(s, field) for s in g.int8])
        return Int8Shard(cat("q"), cat("scales"), cat("err"),
                         self.int8_resident_norms(), cat("qnorm_sq"))

    def int8_resident_norms(self) -> np.ndarray:
        """norms_sq of :meth:`int8_resident` alone — the only int8 channel
        mutations touch, so engines refresh just this (the codes/scales/err
        upload happens once, not per delete)."""
        g = self._gen
        if g.int8 is None:
            raise RuntimeError("int8 tier not materialized; call ensure_tier('int8')")
        norms = np.concatenate([s.norms_sq for s in g.int8]).copy()
        for i, s in enumerate(g.shards):
            start, nv = s.meta.row_start, s.meta.n_valid
            dead = g.main_tomb[start: start + nv]
            if dead.any():
                norms[i * self.rows_per_shard: i * self.rows_per_shard + nv][dead] = np.inf
        return norms

    # ------------------------------------------------- pinning / generations
    def snapshot(self) -> StoreView:
        """Pin the live generation and return a read view of it. The
        generation (shards, tiers, id tables) cannot be garbage-collected
        until the view is released — searches hold one across their whole
        execution so a concurrent compaction swap never invalidates the
        arrays mid-scan."""
        with self._lock:
            g = self._gen
            g.refs += 1
        return StoreView(self, g)

    def _unpin(self, g: _Generation) -> None:
        collect = False
        with self._lock:
            g.refs -= 1
            if g.obsolete and g.refs <= 0 and not g.collected:
                g.collected = True
                collect = True
                if g in self._retired:
                    self._retired.remove(g)
        if collect:
            self._gc_generation(g)

    def _retire(self, g: _Generation) -> None:
        """Mark a superseded generation for GC — immediate if unpinned,
        deferred to the last :meth:`_unpin` otherwise."""
        collect = False
        with self._lock:
            g.obsolete = True
            if g.refs <= 0 and not g.collected:
                g.collected = True
                collect = True
            elif not g.collected and g not in self._retired:
                self._retired.append(g)
        if collect:
            self._gc_generation(g)

    def _gc_generation(self, g: _Generation) -> None:
        """Remove a dead generation's files. Generation k>0 owns its whole
        ``gen_<k>/`` directory; generation 0 shares the store root, so only
        the files its manifest names (plus its journal) are removed — never
        the CURRENT pointer or the live generation's subdirectory."""
        if self._directory is None or g.directory is None:
            return
        if os.path.abspath(g.directory) != os.path.abspath(self._directory):
            shutil.rmtree(g.directory, ignore_errors=True)
            return
        for m in g.manifest.shards:
            for fname in m.files.values():
                _try_remove(os.path.join(self._directory, fname))
        if g.manifest.row_ids_file:
            _try_remove(os.path.join(self._directory, g.manifest.row_ids_file))
        _try_remove(os.path.join(self._directory, JOURNAL_NAME))
        _try_remove(os.path.join(self._directory, MANIFEST_NAME))

    # ------------------------------------------------------------ compaction
    def compact(self) -> dict:
        """Fold delta rows + tombstones into a fresh immutable generation
        and atomically switch readers to it.

        The swap is the "atomic by pointer" build-switch: the new
        generation is fully written and fsync'd in its own directory
        (shards, norms, row CRCs, id table, int8 tier if the old
        generation had one, manifest, journal seeded with any mutations
        that arrived during the build), and only then does the root
        ``CURRENT`` file flip — one ``os.replace``. A crash anywhere
        before that point leaves the old generation untouched (the orphan
        directory is swept at next open); a crash anywhere after it leaves
        the new generation complete. Geometry is preserved
        (rows_per_shard, padded_dim), so compiled streamed steps carry
        over — zero recompiles.

        Mutations never block searches: the build phase runs without the
        store lock (old shards are immutable, the delta is append-only);
        only the final drain-and-swap takes it, and searches do not take
        the lock at all — in-flight ones keep scanning their pinned
        generation. Returns a stats dict (also visible via
        :meth:`compaction_status`).
        """
        inj = self._active_injector()

        def crash(site: str) -> None:
            if inj is not None:
                inj.crash_point(site)

        with self._lock:
            if self._compact_state["running"]:
                raise RuntimeError("compaction already running")
            self._compact_state["running"] = True
            self._compact_state["error"] = None
        try:
            stats = self._compact_impl(crash)
            with self._lock:
                self._compact_state["compactions"] += 1
                self._compact_state["last"] = stats
            return stats
        except BaseException as e:
            with self._lock:
                self._compact_state["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            with self._lock:
                self._compact_state["running"] = False

    def _compact_impl(self, crash) -> dict:
        t0 = time.monotonic()
        crash("compact.begin")
        # -- snapshot the fold point (everything before it goes into the new
        #    generation's shards; everything after drains into its journal)
        with self._lock:
            g = self._gen
            snap_delta = g.n_delta
            snap_main_tomb = g.main_tomb.copy()
            snap_delta_tomb = list(g.delta_tomb[:snap_delta])
            snap_next_id = self._next_id
            want_int8 = g.int8 is not None
        dim = self.dim
        rows = self.rows_per_shard
        padded_dim = self.padded_dim
        rid_src = (g.row_ids if g.row_ids is not None
                   else np.arange(g.n_main, dtype=np.int64))

        # -- collect live rows + their external ids (lock-free: main shards
        #    are immutable, delta rows are append-only and we stop at the
        #    snapshot boundary)
        vec_parts: list[np.ndarray] = []
        id_parts: list[np.ndarray] = []
        for s in g.shards:
            start, nv = s.meta.row_start, s.meta.n_valid
            if nv == 0:
                continue
            alive = ~snap_main_tomb[start: start + nv]
            if not alive.any():
                continue
            vec_parts.append(np.asarray(s.vectors[:nv])[alive][:, :dim])
            id_parts.append(rid_src[start: start + nv][alive])
        alive_j = [j for j in range(snap_delta) if not snap_delta_tomb[j]]
        if alive_j:
            vec_parts.append(
                np.stack([g.delta[j] for j in alive_j])[:, :dim])
            id_parts.append(np.asarray([g.delta_ids[j] for j in alive_j],
                                       dtype=np.int64))
        if vec_parts:
            v_live = np.concatenate(vec_parts)
            ext_ids = np.concatenate(id_parts)
        else:
            v_live = np.zeros((0, dim), dtype=np.float32)
            ext_ids = np.zeros(0, dtype=np.int64)
        identity = bool(np.array_equal(ext_ids,
                                       np.arange(ext_ids.shape[0])))
        new_num = g.number + 1

        # -- materialize the new generation offline (equal geometry: the
        #    compiled streamed steps must survive the swap)
        gen_name = gen_dir = None
        if self._directory is not None:
            gen_name = GEN_DIR_FMT.format(new_num)
            gen_dir = os.path.join(self._directory, gen_name)
            if os.path.isdir(gen_dir):  # leftovers of a crashed compaction
                shutil.rmtree(gen_dir)
            os.makedirs(gen_dir)
        new_shards, metas = _materialize_shards(
            v_live, rows, padded_dim, gen_dir, durable=True)
        crash("compact.after_shards")
        row_ids_file = ""
        if not identity and gen_dir is not None:
            row_ids_file = ROW_IDS_NAME
            np.save(os.path.join(gen_dir, row_ids_file), ext_ids)
            _fsync_file(os.path.join(gen_dir, row_ids_file))
        manifest = Manifest(
            dim=dim, padded_dim=padded_dim, rows_per_shard=rows,
            n_valid=int(v_live.shape[0]), dtype=g.manifest.dtype,
            tiers=(F32_TIER,), shards=tuple(metas), generation=new_num,
            next_id=snap_next_id, row_ids_file=row_ids_file)
        new_gen = _Generation(new_num, manifest, new_shards,
                              directory=gen_dir if gen_dir is not None
                              else self._directory,
                              row_ids=None if identity else ext_ids)
        if self._directory is None:
            new_gen.directory = None
        if want_int8:
            # re-quantize so streamed scans return to 1 B/element over the
            # folded rows (delta rows had no int8 representation)
            self._quantize_generation(new_gen)
        elif gen_dir is not None:
            manifest.save(gen_dir)
        crash("compact.after_manifest")

        # -- drain mutations that arrived during the build, swap the
        #    pointer, and retire the old generation
        with self._lock:
            if g.int8 is not None and new_gen.int8 is None:
                # the tier appeared mid-build (ensure_tier raced us)
                self._quantize_generation(new_gen)
            new_journal = None
            if self._directory is not None:
                new_journal = Journal(os.path.join(gen_dir, JOURNAL_NAME),
                                      self._active_injector)
            drained = 0
            for j in range(snap_delta, g.n_delta):
                row = np.asarray(g.delta[j][None, :dim], dtype=np.float32)
                gid = g.delta_ids[j]
                if new_journal is not None:
                    new_journal.append(encode_upsert(gid, row))
                padded = np.zeros((1, padded_dim), dtype=np.float32)
                padded[:, :dim] = row
                self._apply_upsert_gen(new_gen, padded,
                                       np.asarray([gid], dtype=np.int64))
                drained += 1
            dead_ids: list[int] = []
            newly_dead = np.flatnonzero(g.main_tomb & ~snap_main_tomb)
            dead_ids.extend(int(rid_src[p]) for p in newly_dead)
            dead_ids.extend(
                g.delta_ids[j] for j in range(snap_delta)
                if g.delta_tomb[j] and not snap_delta_tomb[j])
            dead_ids.extend(
                g.delta_ids[j] for j in range(snap_delta, g.n_delta)
                if g.delta_tomb[j])
            if dead_ids:
                if new_journal is not None:
                    new_journal.append(encode_delete(dead_ids))
                lut = self._lut_of(new_gen)
                self._tombstone_gen(new_gen,
                                    [int(lut[gid]) for gid in dead_ids])
                drained += 1
            crash("compact.before_current")
            if self._directory is not None:
                write_current(self._directory, gen_name)
            crash("compact.after_current")
            old_journal = self._journal
            self._journal = new_journal
            self._gen = new_gen  # THE swap: one reference assignment
            self._mutations += 1  # engines rebuild their device views
        if old_journal is not None:
            old_journal.close()
        reclaimed = (g.n_main + snap_delta) - int(v_live.shape[0])
        self._retire(g)
        crash("compact.after_gc")
        return {
            "generation": new_num,
            "n_live": int(v_live.shape[0]),
            "delta_folded": snap_delta,
            "rows_reclaimed": int(reclaimed),
            "drained_during_build": drained,
            "duration_s": round(time.monotonic() - t0, 6),
        }

    def compact_async(self) -> threading.Thread | None:
        """Kick off :meth:`compact` on a daemon thread (the serving
        trigger). Returns the thread, or None if a compaction is already
        running. Errors land in :meth:`compaction_status` ``["error"]``."""
        with self._lock:
            if self._compact_state["running"]:
                return None

        def run():
            try:
                self.compact()
            except RuntimeError:
                pass  # lost the arm race to another trigger
            except BaseException:
                pass  # recorded in _compact_state["error"] by compact()

        t = threading.Thread(target=run, name="store-compactor", daemon=True)
        t.start()
        return t

    def _maybe_auto_compact_locked(self) -> None:
        if self.auto_compact_pending is None:
            return
        if self._compact_state["running"]:
            return
        g = self._gen
        pending = g.n_delta + g.dead_main + g.dead_delta
        if pending >= self.auto_compact_pending:
            self.compact_async()

    def compaction_status(self) -> dict:
        """Live compaction/generation state (rides the scheduler's health
        block and the serving status endpoint)."""
        with self._lock:
            g = self._gen
            return {
                "running": bool(self._compact_state["running"]),
                "compactions": int(self._compact_state["compactions"]),
                "generation": g.number,
                "pending_delta": g.n_delta,
                "tombstones": int(g.dead_main + g.dead_delta),
                "auto_compact_pending": self.auto_compact_pending,
                "retired_pinned": len(self._retired),
                "last": self._compact_state["last"],
                "error": self._compact_state["error"],
            }

    def close(self) -> None:
        """Release the journal file handle (reads stay valid)."""
        if self._journal is not None:
            self._journal.close()
