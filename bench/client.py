"""The load generator: a child process of the benchmark that never imports JAX.

    python3 -m bench.client <spec.json>

It drives ``POST /v1/collections/<name>/search`` over persistent HTTP/1.1
connections, one query per request, with bodies encoded before the window
so that the client's own CPU does not set the rate. Stdlib and numpy only.

    closed  each connection sends its next request when the answer lands;
    open    requests are due on a fixed schedule whatever the server does;
            latency is timed from the time each request was *due*, so a
            stall of the server or of this generator shows in every request
            it delays, and the generator's lateness (sent - due) is reported.

Protocol with the parent, one line each on stdout/stdin: after set-up
(bodies encoded, connections open, a few warm-up requests answered) the
client prints ``READY``; the parent answers ``GO <t>``, the
``time.monotonic()`` at which the window opens. The client stops sending
at ``t + seconds`` (closed) or sends the schedule (open), waits up to
``grace_s`` for the answers still due, writes its records to
``spec["out"]`` and prints ``DONE``.

The spec names a plan file (npz): ``pool`` (P, d) float32 query vectors,
``order`` (the pool entry of each request, cycled in a closed loop),
``offsets`` (open loop: each request's due time after the window opens) and
``keep`` (pool entries whose answers are kept whole for the check).
"""
from __future__ import annotations

import asyncio
import gc
import json
import math
import sys
import time

import numpy as np

#: a request that never got an answer has this status
NO_ANSWER = -1
#: requests sent before the window, on as many connections
WARM_REQUESTS = 16


class Connection:
    """One persistent HTTP/1.1 connection; reopens after a failure."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.reader = self.writer = None

    async def open(self) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            self.host, self.port)

    async def call(self, raw: bytes) -> tuple[int, bytes]:
        if self.writer is None or self.writer.is_closing():
            await self.open()
        try:
            self.writer.write(raw)
            await self.writer.drain()
            head = await self.reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ", 2)[1])
            n = 0
            for line in head.split(b"\r\n")[1:]:
                if line[:15].lower() == b"content-length:":
                    n = int(line[15:])
            body = await self.reader.readexactly(n) if n else b""
        except (OSError, asyncio.IncompleteReadError, ValueError, IndexError):
            await self.close()
            return NO_ANSWER, b""
        return status, body

    async def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
            try:
                await self.writer.wait_closed()
            except OSError:
                pass
            self.writer = None


def encode_requests(pool: np.ndarray, path: str, host: str, k: int) -> list:
    """Whole HTTP requests, one per pool entry. ``repr`` of a float32 widened
    to float64 parses back to the same float32, so the server sees exactly
    the pool's vectors."""
    out = []
    for row in pool:
        body = ('{"queries": [' + ", ".join(map(repr, row.tolist()))
                + f'], "k": {k}}}').encode()
        head = (f"POST {path} HTTP/1.1\r\nHost: {host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        out.append(head + body)
    return out


def answered(status: int, body: bytes) -> bool:
    """A 200 that carries a top-k (not the shed envelope) is an answer."""
    return status == 200 and b'"shed": false' in body


class Records:
    """One row per request: pool entry, due, sent, done, status, answered."""

    def __init__(self, keep: np.ndarray):
        self.rows: list[list] = []
        self.keep = keep
        self.kept: dict[int, bytes] = {}

    def start(self, pool_idx: int, due: float, sent: float) -> int:
        self.rows.append([pool_idx, due, sent, math.nan, NO_ANSWER, False])
        return len(self.rows) - 1

    def finish(self, i: int, status: int, body: bytes) -> None:
        row = self.rows[i]
        row[3] = time.monotonic()
        row[4] = status
        row[5] = answered(status, body)
        if self.keep[row[0]]:
            self.kept[i] = body


async def closed_loop(conns, raws, order, rec: Records, t_start, t_end,
                      grace_s) -> None:
    nxt = 0

    async def worker(conn: Connection) -> None:
        nonlocal nxt
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            i = nxt
            nxt += 1
            p = int(order[i % len(order)])
            r = rec.start(p, now, now)
            status, body = await conn.call(raws[p])
            rec.finish(r, status, body)

    await asyncio.sleep(max(0.0, t_start - time.monotonic()))
    tasks = [asyncio.ensure_future(worker(c)) for c in conns]
    await asyncio.wait(tasks, timeout=t_end + grace_s - time.monotonic())
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def open_loop(conns, raws, order, offsets, rec: Records, t_start,
                    t_end, grace_s, host, port) -> int:
    """Returns how many connections were opened beyond the pre-opened ones."""
    idle = list(conns)
    extra = 0

    async def fire(p: int, due: float) -> None:
        nonlocal extra
        if idle:
            conn = idle.pop()
        else:
            conn = Connection(host, port)
            extra += 1
        r = rec.start(p, due, time.monotonic())
        status, body = await conn.call(raws[p])
        rec.finish(r, status, body)
        idle.append(conn)

    tasks = []
    for p, off in zip(order.tolist(), offsets.tolist()):
        due = t_start + off
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(fire(p, due)))
    if tasks:
        await asyncio.wait(tasks, timeout=t_end + grace_s - time.monotonic())
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return extra


async def _readline() -> str:
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, sys.stdin.readline)


async def main_async(spec: dict) -> None:
    plan = np.load(spec["plan"])
    pool, order, keep = plan["pool"], plan["order"], plan["keep"]
    raws = encode_requests(pool, spec["path"], spec["host"], spec["k"])
    conns = [Connection(spec["host"], spec["port"])
             for _ in range(spec["connections"])]
    await asyncio.gather(*(c.open() for c in conns))
    # a few warm-up requests: the server's codec and batcher see traffic
    # before the window
    warm = await asyncio.gather(*(
        c.call(raws[int(order[j % len(order)])])
        for j, c in enumerate(conns[:WARM_REQUESTS])))
    bad = [s for s, b in warm if not answered(s, b)]
    if bad:
        raise RuntimeError(f"{len(bad)} warm-up requests were not answered: "
                           f"statuses {sorted(set(bad))}")
    print("READY", flush=True)
    line = (await _readline()).split()
    if not line or line[0] != "GO":
        raise RuntimeError(f"expected GO from the parent, got {line!r}")
    t_start = float(line[1])
    seconds = float(spec["seconds"])
    # no collector pauses inside the window: what set-up made is frozen
    # out of every collection, and the window's garbage waits for the end
    gc.collect()
    gc.freeze()
    gc.disable()
    rec = Records(keep)
    extra = 0
    if spec["loop"] == "closed":
        await closed_loop(conns, raws, order, rec, t_start, t_start + seconds,
                          spec["grace_s"])
    else:
        extra = await open_loop(conns, raws, order, plan["offsets"], rec,
                                t_start, t_start + seconds, spec["grace_s"],
                                spec["host"], spec["port"])
    await asyncio.gather(*(c.close() for c in conns), return_exceptions=True)
    write_records(spec["out"], rec, spec["k"],
                  {"extra_connections": extra,
                   "jax_imported": "jax" in sys.modules})
    print("DONE", flush=True)


def write_records(path: str, rec: Records, k: int, meta: dict) -> None:
    """Every request's row, and the answers of the kept pool entries whole:
    ids -1 and scores nan where an answer never came or did not parse."""
    rows = rec.rows
    kept_req = [i for i, row in enumerate(rows) if rec.keep[row[0]]]
    ids = np.full((len(kept_req), k), -1, np.int64)
    scores = np.full((len(kept_req), k), np.nan, np.float64)
    for j, i in enumerate(kept_req):
        if not rows[i][5]:
            continue
        try:
            ans = json.loads(rec.kept[i])
            got_i = np.asarray(ans["indices"], np.int64).reshape(-1)
            got_s = np.asarray(ans["scores"], np.float64).reshape(-1)
        except (ValueError, KeyError, TypeError):
            continue
        ids[j, : min(k, got_i.size)] = got_i[:k]
        scores[j, : min(k, got_s.size)] = got_s[:k]
    np.savez(
        path,
        pool_idx=np.asarray([r[0] for r in rows], np.int32),
        t_due=np.asarray([r[1] for r in rows], np.float64),
        t_sent=np.asarray([r[2] for r in rows], np.float64),
        t_done=np.asarray([r[3] for r in rows], np.float64),
        status=np.asarray([r[4] for r in rows], np.int16),
        ok=np.asarray([r[5] for r in rows], bool),
        kept_req=np.asarray(kept_req, np.int64),
        kept_ids=ids,
        kept_scores=scores,
        meta=np.asarray(json.dumps(meta)),
    )


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    asyncio.run(main_async(spec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
