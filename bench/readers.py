"""The arithmetic of the per-layer metrics; ``bench/metrics/<metric>.py``
names which one each metric reads.

Every reader takes the run's records (``harness.run_cell``) and returns a
number, or None where the run holds nothing to read: a metric that reads
the device trace is left out of a run whose trace holds no device op, and
a share of a roofline is never reported as 0.

records
    seconds, answered    the window and the answers completed in it
    dispatches           the collection's batcher dispatches in the window
    misses               executable-cache misses from the window's start to
                         the last answer
    decode_s, encode_s   host seconds in request decode and response encode
                         spans that started in the window, and
    decode_queries,      the requests parsed and the results encoded by them
    encode_queries
    dispatch_rows        queued requests of each dispatch that started in
                         the window
    trace                the reduced device trace of the window (busy_s,
                         window_s, ...) or None
    rows, dim, peaks     the configuration's shape and the chip's peaks
"""
from __future__ import annotations

from bench.work import least_time_s


def rows_per_dispatch(rec: dict):
    if rec["dispatches"] <= 0:
        return None
    return rec["answered"] / rec["dispatches"]


def codec_ms_per_query(rec: dict):
    """Decode time per request parsed plus encode time per result encoded:
    the host cost of one query on the wire, whatever the window's edges cut."""
    if not rec.get("decode_queries") or not rec.get("encode_queries"):
        return None
    return 1e3 * (rec["decode_s"] / rec["decode_queries"]
                  + rec["encode_s"] / rec["encode_queries"])


def executable_misses(rec: dict):
    return rec["misses"]


def _busy_s(rec: dict):
    tr = rec.get("trace")
    return tr["busy_s"] if tr else None


def device_idle_pct(rec: dict):
    busy = _busy_s(rec)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / rec["trace"]["window_s"])


def device_ms_per_query(rec: dict):
    busy = _busy_s(rec)
    if busy is None or rec["answered"] <= 0:
        return None
    return busy * 1e3 / rec["answered"]


def dispatch_roofline(rec: dict):
    """Least time of the window's dispatches (``bench.work``: the exact scan
    of m queries over the corpus at the HBM and bf16 peaks) over the
    device's busy time, in percent. The busy time is the whole compiled
    dispatch, the scan and the top-k merge alike, so this is the share of
    the device's work that the scan's roofline accounts for, not the scan
    kernel's own efficiency."""
    busy = _busy_s(rec)
    if busy is None or rec.get("peaks") is None or not rec.get("dispatch_rows"):
        return None
    least = sum(least_time_s(m, rec["rows"], rec["dim"], rec["peaks"])[0]
                for m in rec["dispatch_rows"])
    return 100.0 * least / busy
