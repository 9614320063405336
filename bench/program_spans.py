"""Per-layer metrics read from the program's own spans (``repro.tracing``).

The program keeps the spans of every request and every dispatch in one
process-wide ring. The run calls these readers in-process after the
window, once the server has stopped and before its state is dropped, so
the ring holds the newest server's whole lifetime: its ``serve`` span and
everything inside it. A program without the tracer (an older checkout)
gives nothing to read, and every reader returns None.

The window is the one the batcher's counter gives ``records["dispatches"]``
for: that many batcher dispatches in start order, from the first request
of the window on. The client answers its warm-up requests (at most
``bench.client.WARM_REQUESTS``, all sent at once) before the window opens
and sends nothing until it does, so the window's first request is the one
after the longest pause with no request in the server among the server's
first ``WARM_REQUESTS + 1``. The harness's own warm-up calls the scheduler
directly and records no ``batch`` span. Where the ring no longer holds all
of the server's lifetime, or fewer dispatches than the window's, a reader
returns None, never a number from part of the window.

A span is ``(name, start, end, id, parent, value, label)`` on
``time.monotonic()``; a dispatch's spans share its id, a request's
``queue`` span names the dispatch that took it as its parent, and a
dispatch's ``worker`` span carries its mode as the label. A metric named
for a mode (``.fqsd``, ``.fdsq``) reads the window's dispatches of that
mode alone, and the requests they served; ``loop_busy_pct`` reads the
whole window, since one event loop serves every mode.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench.client import WARM_REQUESTS
from bench.trace import merged, total

#: the spans in which the event loop works (not the ones it awaits in)
LOOP_SPANS = ("parse", "admit", "encode", "write", "take", "deliver")


@dataclasses.dataclass
class Window:
    spans: list        # every span of the newest server's lifetime
    batches: list      # the window's batch spans, in start order
    previous: list     # the batch span before each of them (None: none)
    dispatch: dict     # dispatch id -> {span name: span}, every dispatch
    queues: list       # queue spans of the requests the window's batches took

    def stage(self, batch, name: str):
        return self.dispatch.get(batch[3], {}).get(name)

    def mode(self, batch) -> str:
        """The mode the dispatch ran in (its ``worker`` span's label)."""
        worker = self.stage(batch, "worker")
        return worker[6] if worker is not None else ""

    def of_mode(self, mode: str) -> list:
        """The window's batch spans whose dispatch ran in `mode`."""
        return [b for b in self.batches if self.mode(b) == mode]


def _start(span) -> float:
    return span[1]


def window_opens(requests: list, warm: int = WARM_REQUESTS) -> float | None:
    """Start of the window's first request: the request after the longest
    pause with no request in flight among the first ``warm + 1``
    (`requests` in start order)."""
    best, opened, last_end = 0.0, None, None
    for r in requests[:warm + 1]:
        if last_end is not None and r[1] - last_end > best:
            best, opened = r[1] - last_end, r[1]
        last_end = r[2] if last_end is None else max(last_end, r[2])
    return opened


def window(records: dict, tracer=None) -> Window | None:
    """The window's spans in `tracer` (the program's process-wide one by
    default), or None where the ring cannot give all of them."""
    if tracer is None:
        try:
            from repro.tracing import TRACER as tracer
        except ImportError:  # a program without the tracer
            return None
    n = records.get("dispatches") or 0
    ring = tracer.snapshot()
    serves = [s for s in ring if s[0] == "serve"]
    if n <= 0 or not serves:
        return None
    lo, hi = serves[-1][1], serves[-1][2]
    if len(ring) == tracer.spans.maxlen and ring[0][2] > lo:
        return None  # the ring dropped spans of this server's lifetime
    spans = [s for s in ring if lo <= s[1] and s[2] <= hi]
    requests = sorted((s for s in spans if s[0] == "request"), key=_start)
    opened = window_opens(requests)
    if opened is None:
        return None
    batches = sorted((s for s in spans if s[0] == "batch"), key=_start)
    first = bisect.bisect_left([b[1] for b in batches], opened)
    if len(batches) - first < n:
        return None
    dispatch: dict = {}
    for s in spans:
        dispatch.setdefault(s[3], {})[s[0]] = s
    ids = {b[3] for b in batches[first:first + n]}
    return Window(
        spans=spans,
        batches=batches[first:first + n],
        previous=[batches[i - 1] if i else None
                  for i in range(first, first + n)],
        dispatch=dispatch,
        queues=[s for s in spans if s[0] == "queue" and s[4] in ids])


def _mean(values: list):
    return sum(values) / len(values) if values else None


def dispatch_lag_ms(records: dict, tracer=None):
    """Mean over the window's FQ-SD dispatches of this worker's start less
    the previous dispatch's worker end, where requests were queued at that
    end: how long the device waited for the host with work at hand."""
    w = window(records, tracer)
    if w is None:
        return None
    queued = [s for s in w.spans if s[0] == "queue"]
    starts = sorted(s[1] for s in queued)
    ends = sorted(s[2] for s in queued)
    fqsd = {b[3] for b in w.of_mode("fqsd")}
    lags = []
    for prev, batch in zip(w.previous, w.batches):
        if batch[3] not in fqsd:
            continue
        before = prev and w.stage(prev, "worker")
        worker = w.stage(batch, "worker")
        if before is None or worker is None:
            continue
        t = before[2]
        if bisect.bisect_left(starts, t) - bisect.bisect_right(ends, t) > 0:
            lags.append(worker[1] - t)
    mean = _mean(lags)
    return None if mean is None else 1e3 * mean


def loop_busy_pct(records: dict, tracer=None):
    """Share of the window, from its first dispatch's start to its last
    one's end, in which the event loop ran one of LOOP_SPANS."""
    w = window(records, tracer)
    if w is None:
        return None
    lo, hi = w.batches[0][1], w.batches[-1][2]
    if hi <= lo:
        return None
    busy = merged(((s[1], s[2]) for s in w.spans if s[0] in LOOP_SPANS), lo, hi)
    return 100.0 * total(busy) / (hi - lo)


def dispatch_fill_pct(records: dict, tracer=None):
    """Rows the window's FQ-SD dispatches served over the rows they ran,
    padding included, in percent."""
    w = window(records, tracer)
    if w is None:
        return None
    real = padded = 0
    for batch in w.of_mode("fqsd"):
        worker, prepare = w.stage(batch, "worker"), w.stage(batch, "prepare")
        if worker is not None and prepare is not None:
            real += worker[5]
            padded += prepare[5]
    return 100.0 * real / padded if padded else None


def queue_wait_ms(records: dict, tracer=None):
    """Mean time a request of the window's FD-SQ dispatches waited in the
    batcher's queue."""
    w = window(records, tracer)
    if w is None:
        return None
    fdsq = {b[3] for b in w.of_mode("fdsq")}
    mean = _mean([s[2] - s[1] for s in w.queues if s[4] in fdsq])
    return None if mean is None else 1e3 * mean


def dispatch_host_ms(records: dict, tracer=None):
    """Mean host time of a window FD-SQ dispatch on the worker thread less
    its wait for the device: stacking and padding, planning and launch, and
    the accounting after the results came back."""
    w = window(records, tracer)
    if w is None:
        return None
    host = []
    for batch in w.of_mode("fdsq"):
        worker, wait = w.stage(batch, "worker"), w.stage(batch, "wait")
        if worker is not None and wait is not None:
            host.append((worker[2] - worker[1]) - (wait[2] - wait[1]))
    mean = _mean(host)
    return None if mean is None else 1e3 * mean
