"""One run of one cell: set up, serve a measured window, check the answers.

In order, in one process that holds the chip:

1. draw the corpus and the query pool from the seed (``bench.data``);
2. build the collection as the serving CLI does, ``api.Router.create``
   with the configuration's engine arguments, and start
   ``repro.server.KnnServer`` in-process on an ephemeral port;
3. warm every executable the traffic can reach: one dispatch per
   (plan mode, padded batch) the server's scheduler can choose for 1 to
   ``max_batch`` queued requests;
4. start the load generator (``bench.client``) as a child process, which
   never imports JAX; it encodes its requests, opens its connections and
   sends a few warm-up requests before the window;
5. measure for ``seconds``; with ``trace`` the profiler records the window
   and ``bench.spans`` wraps the host work of the served path;
6. read the device's peak memory, stop the server, free the program's
   state, and hold the answers of the sampled requests to the float64
   reference (``bench.reference``).
"""
from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import shutil
import sys
import threading
import time
from collections import deque

import numpy as np

from bench import cell as cellmod
from bench import data, reference, trace as tracemod
from bench.cell import BENCH_DIR, ROOT, Cell

CACHE_DIR = ROOT / ".bench_cache"
#: seconds the client waits after the window for answers still due
GRACE_S = 60.0
#: seconds set-up of the client may take (encoding, connections, warm-up)
READY_TIMEOUT_S = 300.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool):
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep it out of
    # paths shared with other runs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform!r});"
                            " the benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept, so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def limits(cell_name: str) -> dict:
    """The limit of each compared number, ``bench/limits/<cell>.json``."""
    path = BENCH_DIR / "limits" / f"{cell_name}.json"
    return json.loads(path.read_text())["limits"]


# --------------------------------------------------------------- the plan
def make_plan(cell: Cell, seed: int, seconds: float, pool: np.ndarray) -> dict:
    """Which pool entry each request sends, when (open loop), and which
    entries' answers are checked; the same for the same seed.

    Open-loop arrivals are one Poisson schedule for every seed, drawn from
    a fixed generator: the seed draws which query arrives when, so every
    seed offers the same work at the same times, and the spread of a tail
    between runs is the system's, not the schedule's."""
    tr = cell.traffic
    size = len(pool)
    rng = np.random.default_rng([seed, 3])
    perm = rng.permutation(size).astype(np.int32)
    keep = np.zeros(size, bool)
    plan = {"pool": pool}
    if tr["loop"] == "closed":
        plan["order"] = perm
        span = min(size, int(tr["sample_span"]))
        keep[perm[rng.choice(span, int(tr["sample"]), replace=False)]] = True
    elif tr["loop"] == "open":
        if tr["arrivals"] != "poisson":
            raise cellmod.CellError(f"unknown arrivals {tr['arrivals']!r}")
        n = int(round(float(tr["rate_qps"]) * seconds))
        gaps = np.random.default_rng(0).exponential(1.0, n + 1)
        plan["offsets"] = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
        plan["order"] = perm[np.arange(n) % size]
        picks = rng.choice(n, min(n, int(tr["sample"])), replace=False)
        keep[plan["order"][picks]] = True
    else:
        raise cellmod.CellError(f"unknown loop {tr['loop']!r}")
    plan["keep"] = keep
    return plan


# ------------------------------------------------------------------ set-up
def build_router(cell: Cell, x: np.ndarray):
    """The collection, built as the serving CLI builds it."""
    from repro.api import Router

    cfg = cell.config
    router = Router()
    router.create(cfg["name"], x, metric=cfg["metric"], **cfg["engine"])
    return router


def make_server(cell: Cell, router):
    """The HTTP front end over the collection, with the deployment's
    scheduler and admission settings."""
    from repro.server import KnnServer

    return KnnServer(router, host="127.0.0.1", port=0, **cell.config["server"])


def warm(server, cell: Cell, pool: np.ndarray) -> int:
    """Dispatch once per (mode, padded batch) the scheduler can choose for
    1..max_batch queued requests; returns how many dispatches that was."""
    from repro.api import SearchRequest
    from repro.core.partition import next_pow2

    sched = server.schedulers[cell.config["name"]]
    k = int(cell.traffic["k"])
    reqs = [SearchRequest(queries=pool[i % len(pool)], k=k, rid=i)
            for i in range(sched.max_batch)]
    shapes = {}
    for b in range(1, sched.max_batch + 1):
        mode = sched.choose_mode(deque(reqs[:b]), 0.0)
        shapes.setdefault((mode, next_pow2(b)), b)
    for b in shapes.values():
        sched.dispatch_batch(reqs[:b], None)
    return len(shapes)


# ------------------------------------------------------------------ window
async def serve_window(server, cell: Cell, seconds: float, run_dir,
                       trace_dir, spans) -> dict:
    """Serve the client's window; returns what the parent saw of it."""
    import jax

    name = cell.config["name"]
    tr = cell.traffic
    await server.start()
    spec = {
        "host": server.host, "port": server.port,
        "path": f"/v1/collections/{name}/search",
        "k": int(tr["k"]), "loop": tr["loop"],
        "connections": int(tr["connections"]),
        "seconds": seconds, "grace_s": GRACE_S,
        "plan": str(run_dir / "plan.npz"), "out": str(run_dir / "records.npz"),
    }
    spec_path = run_dir / "client.json"
    spec_path.write_text(json.dumps(spec))
    batcher = server.batchers[name]
    router = server.router
    marks: dict = {}

    def mark(label: str) -> None:
        # on a timer thread: the event loop can be busy for a second at a
        # time encoding answers, and would read the counters late
        marks[label] = (time.monotonic(), batcher.dispatched_batches,
                        router.cache_info()["misses"],
                        {mode: plan["count"] for mode, plan in
                         server.schedulers[name].stats()["per_plan"].items()})

    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "bench.client", str(spec_path), cwd=str(ROOT),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE)
    try:
        t0 = time.monotonic()
        line = await asyncio.wait_for(proc.stdout.readline(), READY_TIMEOUT_S)
        if line.strip() != b"READY":
            raise RuntimeError(f"the client failed in set-up ({line!r})")
        client_setup_s = time.monotonic() - t0
        clock = None
        if trace_dir is not None:
            spans.install(server)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # a hook on every Python call
            options.host_tracer_level = 1  # keeps the bench.* annotations
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            clock = time.monotonic()  # ties the trace's clock to ours
            with jax.profiler.TraceAnnotation("bench.clock"):
                pass
        t_start = time.monotonic() + 0.25
        timers = [threading.Timer(t - time.monotonic(), mark, (label,))
                  for t, label in ((t_start, "window_start"),
                                   (t_start + seconds, "window_end"))]
        for timer in timers:
            timer.start()
        proc.stdin.write(f"GO {t_start!r}\n".encode())
        await proc.stdin.drain()
        line = await asyncio.wait_for(proc.stdout.readline(),
                                      seconds + GRACE_S + 120.0)
        if line.strip() != b"DONE":
            raise RuntimeError(f"the client failed in the window ({line!r})")
        await asyncio.wait_for(proc.wait(), 60.0)
        for timer in timers:
            timer.join()
        misses_after = router.cache_info()["misses"]
        if trace_dir is not None:
            jax.profiler.stop_trace()
            spans.uninstall()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        await server.stop()
    if proc.returncode != 0:
        raise RuntimeError(f"the client exited with {proc.returncode}")
    return {"t_start": t_start, "t_end": t_start + seconds, "marks": marks,
            "misses_after": misses_after, "client_setup_s": client_setup_s,
            "clock": clock}


# -------------------------------------------------------------- reduction
def client_results(path, seconds: float, t_start: float, t_end: float,
                   loop: str) -> dict:
    rec = np.load(path)
    meta = json.loads(str(rec["meta"]))
    if meta["jax_imported"]:
        raise RuntimeError("the load generator imported JAX")
    t_due, t_sent, t_done = rec["t_due"], rec["t_sent"], rec["t_done"]
    ok = rec["ok"]
    if loop == "closed":
        due = t_sent < t_end
    else:
        due = np.ones(len(t_due), bool)
    out = {
        "attempted": int(due.sum()),
        "failed": int((due & ~ok).sum()),
        "answered_in_window": int((ok & (t_done <= t_end)).sum()),
        "extra_connections": meta["extra_connections"],
    }
    # a request that never got an answer misses every latency limit: it
    # counts as answered when the run gave up on it
    gave_up = t_end + GRACE_S
    lat = np.where(ok, t_done - t_due, gave_up - t_due)[due]
    out["latency_s"] = lat
    late = (t_sent - t_due)[due]
    out["lateness_p99_ms"] = float(np.percentile(late, 99) * 1e3) if len(late) else 0.0
    out["lateness_max_ms"] = float(late.max() * 1e3) if len(late) else 0.0
    out["lateness_max_at_s"] = (float(t_due[due][late.argmax()] - t_start)
                                if len(late) else 0.0)
    thirds = np.array_split(lat, 3) if len(lat) >= 3 else []
    out["p95_by_third_ms"] = [float(np.percentile(t, 95) * 1e3) for t in thirds]
    out["p95_ms"] = float(np.percentile(lat, 95) * 1e3) if len(lat) else 0.0
    kept = rec["kept_req"]
    kept = kept[due[kept]]
    out["check"] = (rec["pool_idx"][kept],
                    rec["kept_ids"][np.searchsorted(rec["kept_req"], kept)],
                    rec["kept_scores"][np.searchsorted(rec["kept_req"], kept)])
    return out


def end_to_end(cell: Cell, res: dict, seconds: float, setup_s: float) -> dict:
    """The cell's end-to-end metrics, by name."""
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "throughput_qps":
            v = res["answered_in_window"] / seconds
        elif name.startswith("latency_p") and name.endswith("_ms"):
            q = float(name[len("latency_p"):-len("_ms")])
            v = float(np.percentile(res["latency_s"], q)) * 1e3
        else:
            raise cellmod.CellError(f"the harness cannot measure {name!r}")
        out[name] = {"value": v, "unit": m["unit"]}
    return out


def read_trace(trace_dir, clock: float, lo: float, hi: float) -> dict | None:
    """The reduced trace of the window [lo, hi] (``time.monotonic``), or
    None without device ops; `clock` is the monotonic time of the trace's
    ``bench.clock`` annotation."""
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        return None
    tr = tracemod.read_xspace(files[0])
    anchor = tracemod.anchor(tr)
    if anchor is None:
        return None
    return tracemod.reduce(tr, anchor + lo - clock, anchor + hi - clock)


class CompileLog:
    """JAX's backend compiles (a persistent-cache load counts too) and
    jaxpr traces, each as (``time.monotonic()`` at its end, seconds), so
    that a compile inside the window shows."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax.monitoring

        self.events = {label: [] for label in self.EVENTS.values()}
        self._listener = self._record
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _record(self, event: str, duration: float, **_) -> None:
        label = self.EVENTS.get(event)
        if label is not None:
            self.events[label].append((time.monotonic(), duration))

    def within(self, lo: float, hi: float) -> dict:
        """How many of each, and their seconds, that ended in [lo, hi]."""
        out = {}
        for label, evs in self.events.items():
            got = [d for t, d in evs if lo <= t <= hi]
            out[label] = (len(got), sum(got))
        return out

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listener)


def memory_bytes(devs, key: str = "peak_bytes_in_use") -> int:
    """The allocator's `key` (``peak_bytes_in_use``, ``bytes_in_use``) on
    the fullest of `devs` (0 where the backend keeps no such count)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devs)


# -------------------------------------------------------------------- run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, require_tpu: bool = True, run_dir=None,
             compile_cache=CACHE_DIR / "jax", check_limits=None,
             keep_inputs=False) -> dict:
    """One run; returns the result line's fields (and, with `keep_inputs`,
    the corpus, pool and checked answers under ``"inputs"``)."""
    devs = devices(cell.chips, require_tpu)
    import jax

    from bench.spans import Spans
    from repro.tuning import AutotuneCache, set_default_cache

    if compile_cache is not None:
        enable_compile_cache(compile_cache)
    compiles = CompileLog()
    set_default_cache(AutotuneCache(path=None))  # default blocks, no files
    run_dir = run_dir or CACHE_DIR / "run"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    cfg, tr = cell.config, cell.traffic
    steps = {}

    t = time.monotonic()
    x = data.corpus(seed, cfg["rows"], cfg["dim"], cfg["data"])
    pool = data.query_pool(x, seed, int(tr["pool"]), float(tr["query_noise"]))
    plan = make_plan(cell, seed, seconds, pool)
    np.savez(run_dir / "plan.npz", **plan)
    steps["data_s"] = time.monotonic() - t
    t = time.monotonic()
    server = make_server(cell, build_router(cell, x))
    steps["build_s"] = time.monotonic() - t
    steps["peak_gb_build"] = memory_bytes(devs) / 1e9
    t = time.monotonic()
    steps["warm_dispatches"] = warm(server, cell, pool)
    steps["warm_s"] = time.monotonic() - t
    steps["peak_gb_warm"] = memory_bytes(devs) / 1e9
    steps["in_use_gb_warm"] = memory_bytes(devs, "bytes_in_use") / 1e9
    misses_before = server.router.cache_info()["misses"]

    trace_dir = run_dir / "trace" if trace else None
    spans = Spans()
    # the corpus, the plan and the rest of set-up stay out of the server
    # process's collections during the window
    gc.collect()
    gc.freeze()
    win = asyncio.run(serve_window(server, cell, seconds, run_dir, trace_dir,
                                   spans))
    setup_s = win["t_start"] - t0
    steps["client_setup_s"] = win["client_setup_s"]
    peak = memory_bytes(devs)
    in_use_gb_window = memory_bytes(devs, "bytes_in_use") / 1e9
    res = client_results(run_dir / "records.npz", seconds, win["t_start"],
                         win["t_end"], tr["loop"])
    (ws, d0, m0, p0), (we, d1, m1, p1) = (win["marks"]["window_start"],
                                          win["marks"]["window_end"])
    plans = {mode: n - p0.get(mode, 0) for mode, n in p1.items()
             if n > p0.get(mode, 0)}
    in_window = compiles.within(ws, win["t_end"] + GRACE_S)
    compiles.close()
    dispatches = d1 - d0
    records = {
        "seconds": seconds,
        "answered": res["answered_in_window"],
        "dispatches": dispatches,
        "misses": win["misses_after"] - m0,
        "misses_before_window": m0 - misses_before,
        "rows": cfg["rows"], "dim": cfg["dim"],
        "peaks": None, "trace": None,
    }
    if trace:
        for label in ("decode", "encode"):
            got = spans.within(label, ws, we)
            records[f"{label}_s"] = sum(e - s for _, s, e, _ in got)
            records[f"{label}_queries"] = sum(r for *_, r in got)
        records["dispatch_rows"] = [r for *_, r in spans.within("dispatch", ws, we)]
        records["trace"] = read_trace(trace_dir, win["clock"], win["t_start"],
                                      win["t_end"])
        if devs[0].platform == "tpu":
            records["peaks"] = cellmod.peaks(devs[0].device_kind)
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[{cell.name} seed {seed}] set-up {setup_s:.3f} s: data "
        f"{steps['data_s']:.3f} s, build {steps['build_s']:.3f} s, warm "
        f"{steps['warm_s']:.3f} s ({steps['warm_dispatches']} shapes), client "
        f"{steps['client_setup_s']:.3f} s; executable misses during warm-up "
        f"and client set-up {records['misses_before_window']}; device peak "
        f"{steps['peak_gb_build']:.3f} GB after the build, "
        f"{steps['peak_gb_warm']:.3f} GB after warm-up; in use (the serving "
        f"footprint) {steps['in_use_gb_warm']:.3f} GB after warm-up, "
        f"{in_use_gb_window:.3f} GB after the window")
    log(f"[{cell.name} seed {seed}] window {seconds} s: attempted "
        f"{res['attempted']}, failed {res['failed']}, answered in window "
        f"{res['answered_in_window']}, dispatches {dispatches}, executable "
        f"misses {records['misses']}; generator lateness p99 "
        f"{res['lateness_p99_ms']:.3f} ms, max {res['lateness_max_ms']:.3f} ms"
        f" at {res['lateness_max_at_s']:.3f} s, extra connections "
        f"{res['extra_connections']}; JAX compiles (or cache loads) and "
        f"traces from the window's start to the last answer: "
        f"{in_window['compiles'][0]} ({in_window['compiles'][1]:.3f} s), "
        f"{in_window['traces'][0]} ({in_window['traces'][1]:.3f} s); "
        f"queries by plan mode {plans}; latency p95 {res['p95_ms']!r} ms, by "
        f"thirds of the window {res['p95_by_third_ms']} ms")

    metrics = end_to_end(cell, res, seconds, setup_s) if not trace else {}
    if trace:
        for m in cell.per_layer:
            v = cellmod.load_reader(m["name"])(records)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the program's state is freed before the reference runs
    gc.unfreeze()
    server.router.drop(cfg["name"])
    del server
    gc.collect()
    pool_idx, ids, scores = res["check"]
    t = time.monotonic()
    ref = reference.Reference(x, pool, cfg["metric"], int(tr["k"]), pool_idx)
    readings = ref.compare(pool_idx, ids, scores)
    log(f"[{cell.name} seed {seed}] reference checked {readings.checked} "
        f"answers of {len(np.unique(pool_idx))} queries in "
        f"{time.monotonic() - t:.3f} s")
    lim = limits(cell.name) if check_limits is None else check_limits
    checks = {k: {"value": v, "limit": lim[k]}
              for k, v in readings.numbers().items()}
    correct = (readings.checked > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and records["trace"] is not None:
        device["busy_s"] = records["trace"]["busy_s"]
        device["window_s"] = records["trace"]["window_s"]
        out["breakdown"] = {"device_ops": records["trace"]["device_ops"],
                            "idle_gaps": records["trace"]["idle_gaps"]}
    out["checks"] = checks
    if keep_inputs:
        out["inputs"] = {"x": x, "pool": pool, "check": res["check"],
                         "readings": readings, "reference": ref}
    shutil.rmtree(run_dir, ignore_errors=True)
    return out


def format_checks(checks: dict) -> list:
    """One line per compared number: name, value, limit."""
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            for name, c in checks.items()]


def jsonable(out: dict) -> dict:
    return {k: v for k, v in out.items() if k != "inputs"}

