"""The served path's spans (repro.tracing) and the kernels' scope names.

Each server test drives a real `KnnServer` on an ephemeral port over real
sockets, as tests/test_server.py does, and reads the process-wide ring
after the server stopped: one request's spans share its id and nest inside
its ``request`` span, its ``queue`` span names the dispatch that took it,
and that dispatch's spans nest the same way.
"""
from __future__ import annotations

import asyncio

import jax
import numpy as np
import pytest

from repro.api import Router, SearchRequest
from repro.serving.retrieval import AdaptiveScheduler
from repro.server import KnnServer
from repro.server.batching import ServerClosed
from repro.server.loadgen import Connection, LoadReport
from repro.tracing import TRACER, Tracer

REQUEST_STAGES = ("parse", "admit", "queue", "encode", "write")
WORKER_STAGES = ("prepare", "wait")
SEARCH = "/v1/collections/docs/search"


def _router(n=256, d=16, k=5):
    x = np.random.default_rng(0).standard_normal((n, d)).astype(np.float32)
    router = Router()
    router.create("docs", x, k=k, n_partitions=2)
    return router


def _query(d=16, seed=1, **extra):
    q = np.random.default_rng(seed).standard_normal(d).astype(np.float32)
    return {"queries": q.tolist(), **extra}


def _serve(scenario, **server_kw):
    """Run `scenario(server, connection)` against a fresh server; returns
    the spans recorded from its start to its stop."""
    async def run():
        TRACER.reset()
        async with KnnServer(_router(), port=0, **server_kw) as srv:
            conn = Connection(*srv.address, LoadReport(mode="test",
                                                       duration_s=1))
            try:
                await scenario(srv, conn)
            finally:
                await conn.close()
    asyncio.run(run())
    return TRACER.snapshot()


def _by_id(spans, id_):
    return {s[0]: s for s in spans if s[3] == id_}


def _inside(child, parent):
    return parent[1] <= child[1] <= child[2] <= parent[2]


def _one_search(srv, conn):
    async def go():
        st, _ = await conn.request("POST", SEARCH, _query())
        assert st == 200
    return go()


def test_one_request_records_each_stage_inside_its_request_span():
    spans = _serve(lambda srv, conn: _one_search(srv, conn))
    (queue,) = [s for s in spans if s[0] == "queue"]
    mine = _by_id(spans, queue[3])
    assert sorted(mine) == sorted(("request",) + REQUEST_STAGES)
    request = mine["request"]
    stages = [mine[name] for name in REQUEST_STAGES]
    for stage in stages:
        assert _inside(stage, request)
    # one after another, in the order the handler runs them
    for before, after in zip(stages, stages[1:]):
        assert before[2] <= after[1]
    (serve,) = [s for s in spans if s[0] == "serve"]
    assert _inside(request, serve)


def test_a_queue_span_names_the_dispatch_whose_worker_nests_its_stages():
    spans = _serve(lambda srv, conn: _one_search(srv, conn))
    (queue,) = [s for s in spans if s[0] == "queue"]
    dispatch = _by_id(spans, queue[4])
    assert sorted(dispatch) == sorted(("batch", "take", "deliver", "worker")
                                      + WORKER_STAGES)
    batch, worker = dispatch["batch"], dispatch["worker"]
    for name in WORKER_STAGES:
        assert _inside(dispatch[name], worker)
    for name in ("take", "worker", "deliver"):
        assert _inside(dispatch[name], batch)
    assert dispatch["take"][2] <= worker[1] <= worker[2] <= dispatch["deliver"][1]
    assert dispatch["take"][1] <= queue[2] <= dispatch["take"][2]
    # one request, padded to one row, served by FD-SQ (a queue of one)
    assert batch[5] == worker[5] == dispatch["prepare"][5] == 1
    assert worker[6] == "fdsq"
    assert all(s[6] == "" for s in spans if s[0] != "worker")
    # the handler encodes after the batcher delivered the result
    assert dispatch["deliver"][1] <= _by_id(spans, queue[3])["encode"][1]


def test_padded_rows_are_counted_where_the_batch_is_padded():
    async def three_at_once(srv, conn):
        conns = [Connection(*srv.address, LoadReport(mode="t", duration_s=1))
                 for _ in range(3)]
        # hold the worker so that the three queue up behind one dispatch
        sched = srv.schedulers["docs"]
        real = sched.dispatch_batch
        gate = asyncio.Event()
        loop = asyncio.get_running_loop()

        def held(reqs, clock_s=None):
            asyncio.run_coroutine_threadsafe(gate.wait(), loop).result(5)
            return real(reqs, clock_s)

        sched.dispatch_batch = held
        first = asyncio.ensure_future(conn.request("POST", SEARCH, _query()))
        await asyncio.sleep(0.05)
        rest = [asyncio.ensure_future(c.request("POST", SEARCH, _query(seed=i)))
                for i, c in enumerate(conns)]
        await asyncio.sleep(0.05)
        gate.set()
        assert [st for st, _ in await asyncio.gather(first, *rest)] == [200] * 4
        for c in conns:
            await c.close()

    spans = _serve(three_at_once)
    batches = sorted((s for s in spans if s[0] == "batch"), key=lambda s: s[1])
    assert [b[5] for b in batches] == [1, 3]
    prepare = _by_id(spans, batches[1][3])["prepare"]
    assert prepare[5] == 4  # three rows padded to the next power of two


def test_an_admission_rejected_request_records_admit_and_no_queue():
    async def rejected(srv, conn):
        st, _ = await conn.request("POST", SEARCH, _query())  # warm EWMA
        assert st == 200
        srv.batchers["docs"]._ewma_dispatch_s = 10.0
        st, resp = await conn.request("POST", SEARCH, _query(deadline_ms=5.0))
        assert st == 429 and resp["reason"] == "deadline"

    spans = _serve(rejected)
    requests = sorted((s for s in spans if s[0] == "request"),
                      key=lambda s: s[1])
    assert len(requests) == 2
    mine = _by_id(spans, requests[1][3])
    assert sorted(mine) == ["admit", "parse", "request", "write"]
    assert len([s for s in spans if s[0] == "queue"]) == 1


def test_every_reply_records_its_write_under_its_request():
    async def health(srv, conn):
        st, _ = await conn.request("GET", "/healthz")
        assert st == 200

    spans = _serve(health)
    (request,) = [s for s in spans if s[0] == "request"]
    mine = _by_id(spans, request[3])
    assert sorted(mine) == ["request", "write"]
    assert _inside(mine["write"], request)
    assert not [s for s in spans if s[3] == 0]


def test_a_submit_to_a_closed_batcher_opens_no_queue_span(monkeypatch):
    opened = []
    real = TRACER.span

    def span(name, *args, **kw):
        opened.append(name)
        return real(name, *args, **kw)

    async def run():
        srv = KnnServer(_router(), port=0)
        await srv.start()
        await srv.stop()
        monkeypatch.setattr(TRACER, "span", span)
        with pytest.raises(ServerClosed):
            srv.batchers["docs"].submit(
                SearchRequest(queries=np.zeros(16, np.float32), k=1), rid=7)
    asyncio.run(run())
    assert opened == []


def test_the_ring_keeps_the_newest_spans_up_to_its_bound():
    tracer = Tracer(maxlen=8)
    for i in range(20):
        with tracer.span("x", tracer.new_id(), value=i):
            pass
        assert len(tracer.spans) <= 8
    assert [s[5] for s in tracer.snapshot()] == list(range(12, 20))
    tracer.reset()
    assert tracer.snapshot() == []


def test_a_direct_dispatch_records_a_worker_and_no_batch():
    TRACER.reset()
    router = _router()
    sched = AdaptiveScheduler(router=router, collection="docs")
    q = np.random.default_rng(2).standard_normal((3, 16)).astype(np.float32)
    results = sched.dispatch_batch(
        [SearchRequest(queries=q[i], k=5, rid=i) for i in range(3)], None)
    assert len(results) == 3
    assert "dispatch_ms" not in results[0].stats
    spans = TRACER.snapshot()
    assert not [s for s in spans if s[0] == "batch"]
    (worker,) = [s for s in spans if s[0] == "worker"]
    mine = _by_id(spans, worker[3])
    assert sorted(mine) == sorted(("worker",) + WORKER_STAGES)
    assert worker[5] == 3 and mine["prepare"][5] == 4
    assert worker[6] in AdaptiveScheduler.MODES


def test_scheduler_latencies_are_bounded_per_mode(monkeypatch):
    monkeypatch.setattr(AdaptiveScheduler, "LATENCY_WINDOW", 4)
    sched = AdaptiveScheduler(router=_router(), collection="docs",
                              policy="latency")
    q = np.random.default_rng(3).standard_normal(16).astype(np.float32)
    for i in range(6):
        sched.dispatch_batch([SearchRequest(queries=q, k=5, rid=i)], None)
    per_plan = sched.stats()["per_plan"]["fdsq"]
    assert per_plan["count"] == 6
    assert len(sched._lat_ms["fdsq"]) == 4


@pytest.mark.parametrize("fn,scopes", [
    ("fqsd", ("knn.distance_tile", "knn.chunk_merge")),
    ("fdsq", ("knn.distance_tile", "knn.chunk_merge", "knn.tree_merge")),
])
def test_the_kernel_stages_carry_their_scope_names(fn, scopes):
    from repro.core.fdsq import fdsq_search
    from repro.core.fqsd import fqsd_scan

    q = jax.ShapeDtypeStruct((2, 16), np.float32)
    x = jax.ShapeDtypeStruct((64, 16), np.float32)
    norms = jax.ShapeDtypeStruct((64,), np.float32)
    if fn == "fqsd":
        lowered = fqsd_scan.lower(q, x, norms, k=4, metric="l2", chunk_rows=32)
    else:
        lowered = fdsq_search.lower(q, x, norms, k=4, metric="l2",
                                    n_partitions=4)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text
