"""Host spans around the served path's calls into the program, taken only
in a traced run (``--trace 1``).

The program has no spans of its own, so the benchmark wraps the calls it
makes at each layer boundary, without editing them:

    decode    ``HttpRequest.json`` and ``protocol.parse_search_request``
    encode    ``protocol.encode_result`` and ``protocol.http_response``
    dispatch  ``AdaptiveScheduler.dispatch_batch`` of each served collection

Each span is kept as (label, start, end, rows) on ``time.monotonic``, where
rows counts the queries a span serves (1 for the request parse and the
result encode, the batch for a dispatch, 0 for the byte-level halves), and is
also written into the profiler's trace as a ``bench.<label>`` annotation,
so that device-idle time can be charged to what the host was doing.
"""
from __future__ import annotations

import functools
import threading
import time


class Spans:
    """Installs the wrappers; :meth:`uninstall` puts the originals back."""

    def __init__(self):
        self.records: list = []  # (label, start, end, rows)
        self._lock = threading.Lock()
        self._undo: list = []

    def _wrap(self, label: str, fn, rows=None):
        from jax.profiler import TraceAnnotation

        name = f"bench.{label}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.monotonic()
            with TraceAnnotation(name):
                out = fn(*args, **kwargs)
            rec = (label, t0, time.monotonic(),
                   rows(*args, **kwargs) if rows else 0)
            with self._lock:
                self.records.append(rec)
            return out

        return wrapper

    def _patch(self, owner, attr: str, label: str, rows=None) -> None:
        original = getattr(owner, attr)
        had_own = attr in vars(owner)
        setattr(owner, attr, self._wrap(label, original, rows))
        self._undo.append((owner, attr, original, had_own))

    def install(self, server) -> None:
        from repro.server import protocol

        one = lambda *a, **k: 1  # noqa: E731 -- one query per request
        self._patch(protocol.HttpRequest, "json", "decode")
        self._patch(protocol, "parse_search_request", "decode", rows=one)
        self._patch(protocol, "encode_result", "encode", rows=one)
        self._patch(protocol, "http_response", "encode")
        for sched in server.schedulers.values():
            self._patch(sched, "dispatch_batch", "dispatch",
                        rows=lambda reqs, *a, **k: len(reqs))

    def uninstall(self) -> None:
        while self._undo:
            owner, attr, original, had_own = self._undo.pop()
            if had_own:
                setattr(owner, attr, original)
            else:
                delattr(owner, attr)  # an instance attribute over the class's

    def within(self, label: str, lo: float, hi: float) -> list:
        """Spans of `label` that start in [lo, hi)."""
        with self._lock:
            return [r for r in self.records if r[0] == label and lo <= r[1] < hi]
