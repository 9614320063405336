#!/usr/bin/env python3
"""Bring-up smoke of the exact-kNN serving path on a TPU.

    python3 chip_smoke.py                 # one chip: phases a, b, c
    python3 chip_smoke.py --chips 4       # four chips: the sharded phase only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny [--chips 4]
                                          # CPU rehearsal at toy sizes

Every phase serves requests the way a user does: ``api.Router`` ->
``serving.AdaptiveScheduler`` -> ``ExactKNN.search``, the router built by
``repro.launch.serve._build_router`` from the serving CLI's own flags, the
corpus generated from ``--seed`` by ``repro.data.vector_dataset``. Plans use
the default kernel blocks (a fresh in-memory autotune cache).

    a  GIST width (1M x 960 f32, resident): a bursty stream at k=100 under
       the adaptive policy (fdsq-xla + fqsd-xla), then the int8 tier
       (fqsd-int8) at k=100 and on near-duplicate lookups at k=1, where
       its certificate holds.
    b  the fused Pallas executors on the same corpus: fdsq-pallas at
       k=10 (a queue narrower than 128 lanes), k=100 and k=1024,
       fqsd-int8-pallas at k=100; the planner must refuse the
       int8 kernel's 8192-wide queue at k=1024 rather than serve another
       path.
    c  the out-of-core throughput design: a d=4096 store larger than the
       engine's device budget, streamed from disk through
       fqsd-mmap-streamed and fqsd-int8-mmap-streamed.
    --chips 4: MS-MARCO width (d=769) resident over four chips
       (fdsq-sharded, fqsd-sharded, fdsq-sharded-int8) and ring-streamed
       (fqsd-sharded-int8), compared with the single-device streamed f32
       oracle.

Each phase prints the executors that ran, whether the Pallas calls were
compiled (a ``tpu_custom_call`` in their HLO), the exactness check against
a float64 NumPy brute force, the certified fraction, the executable-cache
counters and its wall time, compilation included. Any failure raises and
exits non-zero. The last line, printed only on a TPU and never with
``--tiny``, is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
WORK_DIR = ROOT / "artifacts" / "chip_smoke"  # gitignored; removed at exit


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int  # phase a/b corpus rows (GIST 1M x 960)
    d: int
    queries: int  # bursty stream length
    burst: int
    trickle: int
    k: int  # the serving k
    k_small: int  # a queue narrower than 128 lanes (serve CLI default k=10)
    k_big: int  # the paper's large cutoff (configs/knn_paper.py: 1024)
    n_stream: int  # phase c corpus rows (d = d_stream)
    d_stream: int
    rows_per_shard: int
    budget_bytes: int  # phase c engine device budget (< the store's bytes)
    n_mesh: int  # --chips 4 corpus rows (MS-MARCO width)
    d_mesh: int
    sample: int  # queries per executor checked against float64


FULL = Sizes(n=1_000_000, d=960, queries=192, burst=64, trickle=8, k=100,
             k_small=10, k_big=1024, n_stream=262_144, d_stream=4096,
             rows_per_shard=16_384, budget_bytes=2 << 30,
             n_mesh=4_000_000, d_mesh=769, sample=8)
TINY = Sizes(n=6000, d=96, queries=72, burst=32, trickle=4, k=10, k_small=4,
             k_big=256, n_stream=4096, d_stream=256, rows_per_shard=1024,
             budget_bytes=1 << 20, n_mesh=8192, d_mesh=40, sample=4)

#: MS-MARCO is 8.84M x 769. vector_dataset draws its noise in float64 at
#: about 20 host bytes per element: 8.84M rows would peak near 136 GB of the
#: four-chip host's 150 GB, and the draw alone takes minutes of the run.
#: 4M rows (12.3 GB f32, 3.6 GB per chip resident) keeps both in bounds.
MSMARCO_ROWS = 8_841_823

#: Relative rounding unit of f32.
F32_U = 2.0 ** -24


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(ok, what="") -> None:
    """Fail the run unless `ok` (unlike ``assert``, never compiled away)."""
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------- reference
def f64_topk(x: np.ndarray, qs: np.ndarray, k: int, block: int = 65536):
    """Exact float64 brute force: (ids, dists) of the k nearest rows of
    every query, ordered by (distance, id)."""
    q64 = qs.astype(np.float64)
    qn = (q64 * q64).sum(1)[:, None]
    best_d = np.full((len(qs), 0), np.inf)
    best_i = np.zeros((len(qs), 0), np.int64)
    for s in range(0, x.shape[0], block):
        xb = x[s: s + block].astype(np.float64)
        dist = qn - 2.0 * (q64 @ xb.T) + (xb * xb).sum(1)[None, :]
        ids = np.broadcast_to(np.arange(s, s + xb.shape[0]), dist.shape)
        cd = np.concatenate([best_d, dist], 1)
        ci = np.concatenate([best_i, ids], 1)
        keep = np.argpartition(cd, min(k, cd.shape[1] - 1), axis=1)[:, :k]
        best_d = np.take_along_axis(cd, keep, 1)
        best_i = np.take_along_axis(ci, keep, 1)
    order = np.lexsort((best_i, best_d), axis=1)
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_d, order, 1))


class Reference:
    """The float64 brute force of one corpus and its query stream, computed
    once per (query, k): a phase checks several executors on overlapping
    samples, and each pass over the corpus costs seconds."""

    def __init__(self, x: np.ndarray, qs: np.ndarray):
        self.x, self.qs, self._memo = x, qs, {}

    def topk(self, rids, k):
        todo = sorted({r for r in rids if (r, k) not in self._memo})
        if todo:
            ids, dists = f64_topk(self.x, self.qs[todo], k)
            for j, r in enumerate(todo):
                self._memo[(r, k)] = (ids[j], dists[j])
        return ([self._memo[(r, k)][0] for r in rids],
                [self._memo[(r, k)][1] for r in rids])

    def eps(self, rid, rows) -> float:
        """f32 rounding of one query's distances to `rows` (row ids):
        sqrt(d) roundings of relative size 2^-24 on terms of magnitude
        (|q| + max|x|)^2, times 4. A single bf16 pass (a TPU's default
        matmul precision) rounds 2^15 times more coarsely."""
        q = self.qs[rid].astype(np.float64)
        xmax = math.sqrt((self.x[rows].astype(np.float64) ** 2).sum(1).max())
        return (4.0 * math.sqrt(self.x.shape[1]) * F32_U
                * (np.linalg.norm(q) + xmax) ** 2)


def check_exact(ref: Reference, rids, ids, scores, k):
    """Hold served top-k lists to the float64 brute force.

    The engine ranks by f32 distances, so two rows whose true distances
    differ by less than the f32 rounding of the computation (`eps`) may
    come back in either order; that is a tie at f32 resolution. Per query,
    every returned id must be a distinct row, its served score must lie
    within eps of its float64 distance, and the j-th returned row's
    float64 distance within 2 eps of the j-th smallest one: the ids equal
    the float64 ranking except among rows whose distances tie within
    2 eps.
    """
    ref_i, ref_d = ref.topk(rids, k)
    worst = {"score_err": 0.0, "rank_gap": 0.0, "eps": 0.0}
    same = 0
    for r, rid in enumerate(rids):
        got = ids[r].astype(np.int64)
        check(len(set(got.tolist())) == k and got.min() >= 0,
              f"query {rid}: ids not {k} distinct rows: {got}")
        q = ref.qs[rid].astype(np.float64)
        true_d = ((ref.x[got].astype(np.float64) - q) ** 2).sum(1)
        eps = ref.eps(rid, np.concatenate([got, ref_i[r]]))
        score_err = float(np.abs(scores[r] - true_d).max())
        rank_gap = float(np.abs(true_d - ref_d[r]).max())
        check(score_err <= eps,
              f"query {rid}: served scores off by {score_err} > eps {eps}")
        check(rank_gap <= 2 * eps,
              f"query {rid}: ranking off by {rank_gap} > 2 eps {2 * eps}; "
              f"got {got[:10]} want {ref_i[r][:10]}")
        same += int((got == ref_i[r]).sum())
        for key, v in (("score_err", score_err), ("rank_gap", rank_gap),
                       ("eps", eps)):
            worst[key] = max(worst[key], v)
    return same / (len(rids) * k), worst


# ----------------------------------------------------------------- serving
def serve_args(extra: list[str]):
    from repro.launch.serve import build_parser

    return build_parser().parse_args(["--mode", "knn", *extra])


def replay(router, qs, *, policy, k, tier, burst, trickle,
           fqsd_min_depth=32):
    """A bursty request stream through the AdaptiveScheduler; results in
    request order."""
    from repro.serving import AdaptiveScheduler, bursty_requests

    sched = AdaptiveScheduler(policy=policy, router=router,
                              collection="default",
                              fqsd_min_depth=fqsd_min_depth)
    reqs = bursty_requests(qs, burst, trickle, k=k, tier=tier)
    out = sorted(sched.serve(reqs), key=lambda r: r.rid)
    check(len(out) == len(qs), (len(out), len(qs)))
    return out, sched.stats()


def adaptive_replays(router, qs, sz):
    """The bursty stream under the adaptive policy, twice. The scheduler's
    clock advances by measured service time, compilation included, so the
    cold pass queues every later arrival behind its first compiles and
    serves them as deep FQ-SD batches; the warm pass then sees the sparse
    trickle as it arrives and serves it through FD-SQ."""
    out = []
    for name in ("cold", "warm"):
        res, st = replay(router, qs, policy="adaptive", k=sz.k, tier="f32",
                         burst=sz.burst, trickle=sz.trickle)
        log(f"  adaptive replay ({name}): mode_switches={st['mode_switches']}"
            f" per_plan={ {m: r['count'] for m, r in st['per_plan'].items()} }")
        out += res
    return out


def verify(label, ref: Reference, results, k, sample, require):
    """Print and check one replay: executors, certified fraction, and the
    float64 exactness of `sample` results per executor."""
    by_exec: dict[str, list] = {}
    for r in results:
        by_exec.setdefault(r.plan.executor, []).append(r)
    missing = set(require) - set(by_exec)
    check(not missing, f"{label}: executors {sorted(missing)} did not run")
    cert = [bool(np.asarray(r.certified)) for r in results
            if r.tier == "int8"]
    picks = {name: rs[:sample] for name, rs in by_exec.items()}
    ref.topk(sorted({r.rid for rs in picks.values() for r in rs}), k)
    for name, picked in sorted(picks.items()):
        ids = np.stack([np.asarray(r.indices) for r in picked])
        scores = np.stack([np.asarray(r.scores) for r in picked])
        match, worst = check_exact(ref, [r.rid for r in picked], ids,
                                   scores, k)
        log(f"  [{label}] executor={name} served={len(by_exec[name])} "
            f"exact_vs_f64=ok sampled={len(picked)} k={k} "
            f"id_match={match:.4f} (rest: f32-resolution ties) "
            f"max_score_err={worst['score_err']:.3g} "
            f"max_rank_gap={worst['rank_gap']:.3g} eps={worst['eps']:.3g}")
    if cert:
        log(f"  [{label}] certified_fraction={np.mean(cert):.4f} "
            f"({sum(cert)}/{len(cert)} int8 results; uncertified ones are "
            "recomputed exactly)")
    return picks


class Phase:
    """Prints a phase's wall time (compilation included) and its
    executable-cache deltas; an exception inside propagates."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.core import cache_info

        self.c0 = cache_info()
        self.t0 = time.perf_counter()
        log(f"== phase {self.name}")
        return self

    def __exit__(self, exc_type, *exc):
        from repro.core import cache_info

        if exc_type is not None:
            log(f"== phase {self.name}: FAILED ({exc_type.__name__})")
            return False
        c1 = cache_info()
        log(f"  [{self.name}] cache_info hits={c1['hits'] - self.c0['hits']} "
            f"misses={c1['misses'] - self.c0['misses']} (compiles) "
            f"evictions={c1['evictions'] - self.c0['evictions']}")
        log(f"  [{self.name}] wall_s_including_compilation="
            f"{time.perf_counter() - self.t0:.1f} (one cold run, not a "
            "benchmark)")
        return False


def check_compiled(label, executors, on_tpu):
    """The fused executors ran compiled Mosaic kernels: their cached
    executables carry a tpu_custom_call (interpret mode leaves none)."""
    import jax

    from repro.core.executors import compiled_texts

    interpret = jax.default_backend() != "tpu"
    for name in executors:
        texts = compiled_texts(name)
        check(texts, f"{label}: no compiled executable for {name}")
        n_kernel = sum("tpu_custom_call" in t for t in texts)
        log(f"  [{label}] {name}: interpret={interpret} "
            f"tpu_custom_call in {n_kernel}/{len(texts)} executables")
        if on_tpu:
            check(not interpret and n_kernel == len(texts),
                  f"{label}: {name} did not run a compiled Mosaic kernel")


def mosaic_precision(on_tpu):
    """Error of an f32 dot inside a Pallas kernel at the default precision
    and at EXACT_PRECISION (HIGHEST), against float64."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.core.distance import EXACT_PRECISION

    rng = np.random.default_rng(5)
    a = rng.standard_normal((128, 1024)).astype(np.float32)
    b = rng.standard_normal((128, 1024)).astype(np.float32)
    ref = a.astype(np.float64) @ b.astype(np.float64).T
    errs = {}
    for name, prec in (("default", None), ("highest", EXACT_PRECISION)):
        def kern(a_ref, b_ref, o_ref, prec=prec):
            o_ref[...] = jax.lax.dot_general(
                a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)

        out = pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            interpret=not on_tpu)(a, b)
        errs[name] = float(np.abs(np.asarray(out) - ref).max())
    bound = 4.0 * math.sqrt(1024) * F32_U * float(np.abs(ref).max() + 1024)
    log(f"  [b] mosaic f32 dot max_abs_err: default={errs['default']:.3g} "
        f"highest={errs['highest']:.3g} (f32-class bound {bound:.3g})")
    check(errs["highest"] <= bound, errs)


# ------------------------------------------------------------------ phases
def phase_a(sz: Sizes, seed: int):
    from repro.data import query_stream, vector_dataset
    from repro.launch.serve import _build_router

    with Phase("a: GIST-width resident, XLA executors"):
        t0 = time.perf_counter()
        x = vector_dataset(sz.n, sz.d, seed=seed)
        log(f"  [a] corpus {sz.n} x {sz.d} f32 = {x.nbytes / 1e9:.2f} GB "
            f"generated in {time.perf_counter() - t0:.1f}s (seed {seed})")
        args = serve_args(["--n", str(sz.n), "--d", str(sz.d),
                           "--k", str(sz.k), "--seed", str(seed),
                           "--int8-depth", str(sz.burst)])
        router, _ = _build_router(args, x=x)
        qs = query_stream(x, sz.queries, seed=seed + 1)
        ref = Reference(x, qs)
        res = adaptive_replays(router, qs, sz)
        verify("a", ref, res, sz.k, sz.sample, ("fdsq-xla", "fqsd-xla"))
        res8, _ = replay(router, qs[: 2 * sz.burst], policy="adaptive",
                         k=sz.k, tier="int8", burst=sz.burst,
                         trickle=sz.trickle)
        verify("a", ref, res8, sz.k, sz.sample, ("fqsd-int8",))
        # near-duplicate lookups (k=1, queries that are corpus rows): the
        # certificate's slack is far below their distance gap, so the int8
        # tier answers them without its exact recompute. Every one of them
        # is checked against float64.
        dups = query_stream(x, sz.burst, seed=seed + 4, noise=0.0)
        res1, _ = replay(router, dups, policy="throughput", k=1,
                         tier="int8", burst=sz.burst, trickle=0)
        verify("a k=1", Reference(x, dups), res1, 1, len(dups),
               ("fqsd-int8",))
        check(any(bool(np.asarray(r.certified)) for r in res1),
              "no int8 result was certified: the certified path went "
              "unchecked")
        del router
        gc.collect()
    return ref


def phase_b(sz: Sizes, seed: int, ref: Reference, on_tpu: bool):
    from repro.core.planner import PlanRefused
    from repro.launch.serve import _build_router

    with Phase("b: fused Pallas executors, same corpus"):
        mosaic_precision(on_tpu)
        args = serve_args(["--n", str(sz.n), "--d", str(sz.d),
                           "--k", str(sz.k), "--seed", str(seed),
                           "--backend", "pallas",
                           "--int8-depth", str(sz.burst)])
        router, _ = _build_router(args, x=ref.x)
        qs = ref.qs
        for k in (sz.k_small, sz.k, sz.k_big):
            res, _ = replay(router, qs[: sz.burst + sz.trickle],
                            policy="adaptive", k=k, tier="f32",
                            burst=sz.burst, trickle=sz.trickle)
            verify(f"b k={k}", ref, res, k, sz.sample, ("fdsq-pallas",))
        res8, _ = replay(router, qs[: sz.burst], policy="throughput",
                         k=sz.k, tier="int8", burst=sz.burst, trickle=0)
        verify(f"b k={sz.k}", ref, res8, sz.k, sz.sample,
               ("fqsd-int8-pallas",))
        refused = None
        try:
            replay(router, qs[:4], policy="throughput", k=sz.k_big,
                   tier="int8", burst=4, trickle=0)
        except PlanRefused as e:
            refused = str(e)
        check(refused is not None,
              f"fqsd-int8-pallas at k={sz.k_big} was not refused by the "
              "planner")
        log(f"  [b k={sz.k_big}] fqsd-int8-pallas refused by the planner: "
            f"{refused}")
        check_compiled("b", ("fdsq-pallas", "fqsd-int8-pallas"), on_tpu)
        del router
        gc.collect()


def phase_c(sz: Sizes, seed: int):
    from repro.data import query_stream, vector_dataset
    from repro.launch.serve import _build_router
    from repro.store import DatasetStore

    with Phase("c: out-of-core streamed store"):
        x = vector_dataset(sz.n_stream, sz.d_stream, seed=seed + 2)
        store_dir = WORK_DIR / "store"
        shutil.rmtree(store_dir, ignore_errors=True)
        t0 = time.perf_counter()
        store = DatasetStore.from_array(x, rows_per_shard=sz.rows_per_shard,
                                        directory=str(store_dir))
        log(f"  [c] disk store {sz.n_stream} x {sz.d_stream}: "
            f"{store.n_shards} shards, f32 {store.nbytes('f32') / 1e9:.2f} GB"
            f" > device budget {sz.budget_bytes / 1e9:.2f} GB "
            f"(written in {time.perf_counter() - t0:.1f}s)")
        check(store.nbytes("f32") > sz.budget_bytes)
        args = serve_args(["--n", str(sz.n_stream), "--d", str(sz.d_stream),
                           "--k", str(sz.k), "--seed", str(seed + 2),
                           "--int8-depth", str(sz.burst)])
        router, _ = _build_router(args, store=store,
                                  device_budget_bytes=sz.budget_bytes)
        qs = query_stream(x, sz.burst, seed=seed + 3)
        ref = Reference(x, qs)
        for tier, want in (("f32", "fqsd-mmap-streamed"),
                           ("int8", "fqsd-int8-mmap-streamed")):
            res, st = replay(router, qs, policy="throughput", k=sz.k,
                             tier=tier, burst=sz.burst, trickle=0)
            verify("c", ref, res, sz.k, sz.sample, (want,))
            gb = {t: b / 1e9 for t, b in st["bytes_scanned"].items() if b}
            log(f"  [c] {want}: bytes streamed {gb} GB, "
                f"transfers={st['transfers']}")
        del router, store
        gc.collect()


def phase_mesh(sz: Sizes, seed: int, n_chips: int, on_tpu: bool):
    import jax

    from repro.core.fqsd import streamed_direct_scan
    from repro.data import query_stream, vector_dataset
    from repro.launch.serve import _build_router
    from repro.store import DatasetStore

    with Phase(f"mesh: MS-MARCO width over {n_chips} chips"):
        if sz.n_mesh < MSMARCO_ROWS:
            log(f"  [mesh] rows cut {MSMARCO_ROWS} -> {sz.n_mesh} (host time "
                "and memory of the float64 corpus draw); width 769 and k kept")
        t0 = time.perf_counter()
        x = vector_dataset(sz.n_mesh, sz.d_mesh, seed=seed)
        log(f"  [mesh] corpus {sz.n_mesh} x {sz.d_mesh} f32 = "
            f"{x.nbytes / 1e9:.2f} GB generated in "
            f"{time.perf_counter() - t0:.1f}s (seed {seed})")
        # 16 equal host-RAM shards: the ring deals shard i to device i mod 4
        rows = -(-sz.n_mesh // 16)
        rows = -(-rows // (128 * n_chips)) * (128 * n_chips)
        store = DatasetStore.from_array(x, rows_per_shard=rows)
        common = ["--n", str(sz.n_mesh), "--d", str(sz.d_mesh),
                  "--k", str(sz.k), "--seed", str(seed),
                  "--int8-depth", str(sz.burst)]
        router, _ = _build_router(serve_args(common), store=store,
                                  devices=n_chips)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:n_chips]]
        log(f"  [mesh] resident bytes_in_use per device: {in_use}")
        if on_tpu:  # the corpus is row-sharded, not staged on one device
            check(min(in_use) >= 0.5 * max(in_use), in_use)
        qs = query_stream(x, sz.queries, seed=seed + 1)
        ref = Reference(x, qs)
        res = adaptive_replays(router, qs, sz)
        picks = verify("mesh", ref, res, sz.k, sz.sample,
                       ("fdsq-sharded", "fqsd-sharded"))
        res8, st8 = replay(router, qs[: sz.burst], policy="throughput",
                           k=sz.k, tier="int8", burst=sz.burst, trickle=0)
        picks.update(verify("mesh", ref, res8, sz.k, sz.sample,
                            ("fdsq-sharded-int8",)))
        log(f"  [mesh] fdsq-sharded-int8 bytes_per_device="
            f"{st8['bytes_per_device']}")
        ring, _ = _build_router(serve_args(common), store=store,
                                devices=n_chips, device_budget_bytes=1)
        resr, str_ = replay(ring, qs[: sz.burst], policy="throughput",
                            k=sz.k, tier="int8", burst=sz.burst, trickle=0)
        picks.update(verify("mesh", ref, resr, sz.k, sz.sample,
                            ("fqsd-sharded-int8",)))
        log(f"  [mesh] fqsd-sharded-int8 bytes_per_device="
            f"{str_['bytes_per_device']}")
        # the single-device streamed f32 oracle on the checked sample
        rids = sorted({r.rid for rs in picks.values() for r in rs})
        t0 = time.perf_counter()
        q_pad = np.pad(qs[rids], ((0, 0), (0, store.padded_dim - sz.d_mesh)))
        oracle = streamed_direct_scan(q_pad, store.shard_source("f32"), sz.k)
        o_ids, o_scores = np.asarray(oracle.indices), np.asarray(oracle.scores)
        log(f"  [mesh] streamed f32 oracle over {len(rids)} queries in "
            f"{time.perf_counter() - t0:.1f}s")
        pos = {rid: j for j, rid in enumerate(rids)}
        for name, picked in sorted(picks.items()):
            same, diff = 0, 0.0
            for r in picked:
                j = pos[r.rid]
                same += np.array_equal(np.asarray(r.indices), o_ids[j])
                gap = float(np.abs(np.asarray(r.scores) - o_scores[j]).max())
                # both lists lie within eps of float64, rank by rank within
                # 2 eps of each other's rows (check_exact): 4 eps in all
                bound = 4 * ref.eps(r.rid, np.concatenate(
                    [np.asarray(r.indices), o_ids[j]]))
                check(gap <= bound, (name, r.rid, gap, bound))
                diff = max(diff, gap)
            log(f"  [mesh] {name} vs oracle: identical id lists "
                f"{same}/{len(picked)}, max score diff {diff:.3g} (within "
                "4 eps; other lists differ only among f32-resolution ties)")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a, b, c; 4: the sharded phase only")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes for a CPU rehearsal; never prints the "
                         "result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repro package is not beside this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke.py: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.tuning import AutotuneCache, set_default_cache

    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); jax {jax.__version__}; "
        f"compile cache {enable_compile_cache()}")
    set_default_cache(AutotuneCache(path=None))  # default blocks, no files
    sz = TINY if args.tiny else FULL
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(sz, args.seed, 4, on_tpu)
        else:
            ref = phase_a(sz, args.seed)
            phase_b(sz, args.seed, ref, on_tpu)
            del ref
            phase_c(sz, args.seed)
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s "
        "(one cold run, compilation included)")
    if args.tiny:
        log("--tiny: rehearsal only, no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
