"""In-process spans of the served search path, on one clock.

One process-wide :data:`TRACER` records what the server, the batcher and
the scheduler do with each request and each dispatch. A span is the tuple

    (name, start, end, id, parent, value, label)

with ``start``/``end`` on ``time.monotonic()``. ``id`` is the request or
the dispatch the span belongs to: every span of one request carries the
request's id, every span of one dispatch the dispatch's id, and both come
from one counter, so they never collide. ``parent`` links the two trees: a
request's ``queue`` span names the dispatch that took it; elsewhere it is
0. ``value`` is a count (rows, for the dispatch spans) and ``label`` a
short string: the dispatch's mode (``fdsq``, ``fqsd``, ``fqsd-int8``) on
its ``worker`` span, else empty.

Request spans (event loop)      request > parse, admit, queue, encode, write
Dispatch spans (event loop)     batch > take, deliver
Dispatch spans (worker thread)  worker > prepare, wait
Server lifetime                 serve (``KnnServer.start`` to ``stop``)

Recording is always on: a closed span is one tuple appended to a bounded
ring (``collections.deque(maxlen=...)``, whose ``append`` is atomic under
the GIL, so no lock is taken). Every span is also entered as a
``jax.profiler.TraceAnnotation`` named ``knn.<name>``: a no-op check while
no profiler runs, and while one does, the span lands on the device
trace's clock, so that every idle gap of the device can be put down to
what the host was doing.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["RING_SPANS", "Span", "TRACER", "Tracer"]

#: spans the ring keeps: those of about 49,000 one-query requests (six a
#: request, and six a dispatch of about 200 rows), four minutes at 200
#: requests a second; at about 184 bytes a span, 55 MB full
RING_SPANS = 300_000

#: prefix of the profiler annotations
PREFIX = "knn."


class Span:
    """An open span; :meth:`close` (or leaving the ``with`` block) records
    it. ``parent`` and ``value`` may be set while it is open."""

    __slots__ = ("_ring", "_note", "name", "id", "parent", "value", "label",
                 "start")

    def __init__(self, ring, name: str, id: int, parent: int, value: int,
                 label: str):
        self._ring = ring
        self._note = TraceAnnotation(PREFIX + name)
        self._note.__enter__()
        self.name, self.id, self.parent = name, id, parent
        self.value, self.label = value, label
        self.start = time.monotonic()

    def close(self) -> None:
        end = time.monotonic()
        self._note.__exit__(None, None, None)
        self._ring.append((self.name, self.start, end, self.id, self.parent,
                           self.value, self.label))

    def drop(self) -> None:
        """End the annotation and record nothing (the span came to nothing)."""
        self._note.__exit__(None, None, None)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Tracer:
    """The ring of closed spans and the id counter."""

    def __init__(self, maxlen: int = RING_SPANS):
        self.spans: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def new_id(self) -> int:
        return next(self._ids)  # atomic under the GIL

    def span(self, name: str, id: int, parent: int = 0, value: int = 0,
             label: str = "") -> Span:
        """Open a span; use it as a context manager or close it by hand."""
        return Span(self.spans, name, id, parent, value, label)

    # the batcher hands its dispatch id to the worker thread, where the
    # scheduler opens the dispatch's worker spans under it
    def set_dispatch(self, id: int) -> None:
        """Name the calling thread's current dispatch (0: none)."""
        self._local.dispatch = id

    def dispatch_id(self) -> int:
        """The calling thread's current dispatch, else a new id (a
        dispatch that no batcher made)."""
        return getattr(self._local, "dispatch", 0) or self.new_id()

    def snapshot(self) -> list:
        """The ring's spans in the order they closed (a copy)."""
        return list(self.spans)

    def reset(self) -> None:
        self.spans.clear()


#: the process-wide tracer the server, the batcher and the scheduler write
TRACER = Tracer()
