"""The per-layer metrics read from the program's spans (``bench.program_spans``),
on a synthetic ring: the window found by the batcher's dispatch count after
the client's warm-up, None where the ring cannot give all of it, and each
metric's arithmetic."""
from __future__ import annotations

import collections
import sys

import pytest

from bench import program_spans
from bench.cell import load_reader

W, A, B, C = 100, 201, 202, 203  # dispatch ids: warm-up, window, window, after


def span(name, start, end, id_=0, parent=0, value=0, label=""):
    return (name, start, end, id_, parent, value, label)


def dispatch(id_, start, end, worker, wait, rows, padded, mode):
    """A dispatch's spans: batch > take, deliver; worker > prepare, wait."""
    return [
        span("take", start, start + 0.01, id_),
        span("prepare", worker[0], worker[0] + 0.01, id_, value=padded),
        span("wait", *wait, id_),
        span("worker", *worker, id_, value=rows, label=mode),
        span("deliver", end - 0.1, end - 0.05, id_),
        span("batch", start, end, id_, value=rows),
    ]


def request(id_, start, end, queued, dispatch_id, loop=()):
    """A request's request and queue spans, and any event-loop spans."""
    return [span("queue", *queued, id_, parent=dispatch_id),
            *(span(name, s, e, id_) for name, s, e in loop),
            span("request", start, end, id_)]


def server(t0=0.0, serve_id=1, mode="fqsd", modes=None):
    """One server's lifetime [t0, t0 + 100]: two warm-up requests answered
    by dispatch W by t0 + 2, a pause, then the window's requests from
    t0 + 10 in dispatches A and B, and dispatch C after them. Every
    dispatch runs in `mode` but those `modes` names (id -> mode)."""
    modes = modes or {}
    t = t0
    spans = []
    spans += request(11, t + 1.0, t + 2.0, (t + 1.0, t + 1.1), W)
    spans += request(12, t + 1.0, t + 2.0, (t + 1.0, t + 1.1), W)
    spans += dispatch(W, t + 1.1, t + 1.9, (t + 1.2, t + 1.8),
                      (t + 1.3, t + 1.7), rows=2, padded=2,
                      mode=modes.get(W, mode))
    for rid, wait_s in ((21, 0.005), (22, 0.004), (23, 0.003)):
        spans += request(rid, t + 10.0, t + 11.2, (t + 10.0, t + 10.0 + wait_s), A)
    spans += dispatch(A, t + 10.0, t + 11.0, (t + 10.05, t + 10.85),
                      (t + 10.1, t + 10.8), rows=3, padded=4,
                      mode=modes.get(A, mode))
    spans += request(24, t + 10.5, t + 12.1, (t + 10.5, t + 11.0), B,
                     loop=[("parse", t + 10.5, t + 10.52),
                           ("admit", t + 10.52, t + 10.53),
                           ("encode", t + 11.0, t + 11.3),
                           ("write", t + 11.3, t + 11.35)])
    spans += request(25, t + 10.6, t + 12.1, (t + 10.6, t + 11.0), B)
    spans += dispatch(B, t + 11.0, t + 12.0, (t + 11.25, t + 11.85),
                      (t + 11.3, t + 11.8), rows=2, padded=2,
                      mode=modes.get(B, mode))
    spans += request(26, t + 12.5, t + 13.5, (t + 12.5, t + 12.6), C,
                     loop=[("parse", t + 13.0, t + 13.1)])
    spans += dispatch(C, t + 12.6, t + 13.4, (t + 12.7, t + 13.3),
                      (t + 12.8, t + 13.2), rows=1, padded=1,
                      mode=modes.get(C, mode))
    spans.append(span("serve", t0, t0 + 100.0, serve_id))
    return spans


class Ring:
    """Stands in for ``repro.tracing.Tracer``: a bounded ring of spans."""

    def __init__(self, spans, maxlen=10_000):
        self.spans = collections.deque(spans, maxlen=maxlen)

    def snapshot(self):
        return list(self.spans)


READERS = {
    "dispatch_lag_ms.fqsd": program_spans.dispatch_lag_ms,
    "loop_busy_pct.fqsd": program_spans.loop_busy_pct,
    "dispatch_fill_pct.fqsd": program_spans.dispatch_fill_pct,
    "queue_wait_ms.fdsq": program_spans.queue_wait_ms,
    "dispatch_host_ms.fdsq": program_spans.dispatch_host_ms,
}
#: the window of two dispatches, A and B
EXPECTED = {
    # A follows W, with nothing queued at W's end: not counted; B follows A
    # with request 24 queued at A's worker end (10.85), 0.4 s later
    "dispatch_lag_ms.fqsd": 400.0,
    # [10, 12]: take A .01, parse+admit .03, deliver A .05, take B with
    # encode+write .35, deliver B .05
    "loop_busy_pct.fqsd": 100.0 * 0.49 / 2.0,
    "dispatch_fill_pct.fqsd": 100.0 * 5 / 6,
    "queue_wait_ms.fdsq": 1e3 * (0.005 + 0.004 + 0.003 + 0.5 + 0.4) / 5,
    "dispatch_host_ms.fdsq": 100.0,
}


def mode_of(name):
    """The mode a metric is named for (``loop_busy_pct`` reads every one)."""
    return name.rsplit(".", 1)[1]


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_its_definition_over_the_window(name):
    ring = Ring(server(mode=mode_of(name)))
    assert READERS[name]({"dispatches": 2}, ring) == pytest.approx(EXPECTED[name])


def test_a_metric_named_for_a_mode_reads_that_modes_dispatches_alone():
    # B ran FD-SQ, every other dispatch FQ-SD
    ring = Ring(server(modes={B: "fdsq"}))
    records = {"dispatches": 2}
    # A alone, 3 rows padded to 4
    assert program_spans.dispatch_fill_pct(records, ring) == \
        pytest.approx(75.0)
    # A follows W with nothing queued; B, which lagged, is FD-SQ
    assert program_spans.dispatch_lag_ms(records, ring) is None
    # requests 24 and 25, which B took
    assert program_spans.queue_wait_ms(records, ring) == \
        pytest.approx(1e3 * (0.5 + 0.4) / 2)
    assert program_spans.dispatch_host_ms(records, ring) == \
        pytest.approx(100.0)
    # one event loop serves both modes
    assert program_spans.loop_busy_pct(records, ring) == \
        pytest.approx(EXPECTED["loop_busy_pct.fqsd"])
    # no dispatch of the mode in the window: no number
    only_fqsd = Ring(server())
    assert program_spans.queue_wait_ms(records, only_fqsd) is None
    assert program_spans.dispatch_host_ms(records, only_fqsd) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_metric_file_names_its_reader(name):
    assert load_reader(name) is READERS[name]


def test_the_window_is_the_dispatch_count_after_the_warm_up():
    ring = Ring(server())
    w = program_spans.window({"dispatches": 2}, ring)
    assert [b[3] for b in w.batches] == [A, B]
    assert [p[3] for p in w.previous] == [W, A]
    assert sorted(q[3] for q in w.queues) == [21, 22, 23, 24, 25]
    w3 = program_spans.window({"dispatches": 3}, ring)
    assert [b[3] for b in w3.batches] == [A, B, C]
    assert program_spans.dispatch_fill_pct({"dispatches": 3}, ring) == \
        pytest.approx(100.0 * 6 / 7)
    # more dispatches than the ring holds after the warm-up: no number
    assert program_spans.window({"dispatches": 4}, ring) is None
    assert program_spans.window({"dispatches": 0}, ring) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_an_older_server_in_the_ring_is_left_out(name):
    # the newest server's ids follow the older one's
    mode = mode_of(name)
    newer = [(n, start, end, id_ + 1000, parent and parent + 1000, *rest)
             for n, start, end, id_, parent, *rest in server(mode=mode)]
    ring = Ring(server(t0=-200.0, serve_id=2, mode=mode) + newer)
    w = program_spans.window({"dispatches": 2}, ring)
    assert [b[3] for b in w.batches] == [A + 1000, B + 1000]
    assert READERS[name]({"dispatches": 2}, ring) == \
        pytest.approx(EXPECTED[name])


def test_a_ring_that_dropped_part_of_the_servers_lifetime_gives_none():
    spans = server()
    kept = spans[3:]  # the first warm-up request's spans were evicted
    ring = Ring(kept, maxlen=len(kept))
    assert program_spans.window({"dispatches": 2}, ring) is None
    for read in READERS.values():
        assert read({"dispatches": 2}, ring) is None
    # the same spans in a ring that never filled were never evicted
    assert program_spans.window({"dispatches": 2}, Ring(spans, len(spans) + 1))


def test_a_program_without_the_tracer_gives_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)  # import fails
    for read in READERS.values():
        assert read({"dispatches": 2}) is None


def test_no_server_in_the_ring_gives_none():
    ring = Ring([s for s in server() if s[0] != "serve"])
    for read in READERS.values():
        assert read({"dispatches": 2}, ring) is None


def test_the_window_opens_after_the_longest_pause_with_nothing_in_flight():
    requests = [span("request", 0.0, 1.0), span("request", 0.1, 1.2),
                span("request", 1.3, 1.4), span("request", 5.0, 5.5),
                span("request", 5.2, 5.6)]
    assert program_spans.window_opens(requests) == 5.0
    # only the first warm + 1 requests are searched for the pause
    assert program_spans.window_opens(requests, warm=2) == 1.3
    assert program_spans.window_opens(requests[:1]) is None
