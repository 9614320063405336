"""Reduce a profiler trace of the measured window to device busy time,
idle gaps by what the host was doing, and the device operations that took
the most time.

The run writes one host annotation, ``bench.clock``, at a known
``time.monotonic`` right after the trace starts, which places the window
on the trace's clock, and wraps the host work of the served path in
``bench.<label>`` annotations (``bench.spans``). Everything here is plain
interval arithmetic on (start, end) pairs in seconds, so it can be checked
on synthetic intervals; :func:`read_xspace` is the only part that knows the
trace's file format.
"""
from __future__ import annotations

import collections
import dataclasses

#: host annotations the benchmark writes; the rest of a trace is ignored
PREFIX = "bench."
#: the line of a TPU device plane that holds one event per executed op
DEVICE_OPS_LINE = "XLA Ops"
#: which host span a device-idle second is charged to, first match wins
GAP_PRIORITY = ("dispatch", "encode", "decode")
NO_SPAN = "no_host_span"


@dataclasses.dataclass
class Trace:
    """Events of one trace, times in seconds on the trace's clock."""

    device_ops: dict  # device plane name -> [(op name, start, end)]
    host_spans: list  # [(label without the prefix, start, end)]


def merged(intervals, lo: float, hi: float) -> list:
    """The union of `intervals`, clipped to [lo, hi], as sorted disjoint
    (start, end) pairs."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(p) for p in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy: list, lo: float, hi: float) -> list:
    """The gaps of sorted disjoint `busy` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """`a` minus `b`, both sorted disjoint interval lists."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if e > s:
            out.append((s, e))
    return out


def attribute_gaps(gaps: list, host_spans, lo: float, hi: float) -> dict:
    """Seconds of device-idle time by what the host was doing: each idle
    second goes to the first label of GAP_PRIORITY whose span covers it,
    else to NO_SPAN."""
    by_label = collections.defaultdict(list)
    for label, s, e in host_spans:
        by_label[label].append((s, e))
    left = list(gaps)
    out = {}
    for label in GAP_PRIORITY:
        cover = merged(by_label.get(label, ()), lo, hi)
        hit = intersect(left, cover)
        if hit:
            out[label] = total(hit)
        left = subtract(left, cover)
    if left:
        out[NO_SPAN] = total(left)
    return out


def anchor(trace: Trace) -> float | None:
    """The trace time of the ``bench.clock`` annotation."""
    for label, s, _ in trace.host_spans:
        if label == "clock":
            return s
    return None


def self_times(events, lo: float, hi: float) -> collections.Counter:
    """Seconds each op name ran within [lo, hi] less the time of the ops
    nested in it (a while loop holds its body's ops): every device second
    is counted once."""
    out = collections.Counter()
    stack: list = []  # [name, end, clipped duration, children's time]

    def close(entry) -> None:
        out[entry[0]] += entry[2] - entry[3]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        d = max(0.0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][3] += d
        stack.append([name, e, d, 0.0])
    while stack:
        close(stack.pop())
    return out


def short_name(op: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name and the start of its result shape and opcode."""
    name, sep, rest = op.partition(" = ")
    return f"{name.lstrip('%')} = {rest[:80]}" if sep else op[:100]


def reduce(trace: Trace, lo: float, hi: float, top: int = 10) -> dict | None:
    """busy_s (averaged over the devices), window_s, the device ops by self
    time and the idle time by host span, within [lo, hi] on the trace's
    clock; None when the trace holds no device op in the window."""
    if not trace.device_ops:
        return None
    busy_each, ops = [], collections.Counter()
    gaps_all: list = []
    for events in trace.device_ops.values():
        busy = merged(((s, e) for _, s, e in events), lo, hi)
        busy_each.append(total(busy))
        gaps_all.extend(complement(busy, lo, hi))
        for name, sec in self_times(events, lo, hi).items():
            if sec > 0:
                ops[short_name(name)] += sec
    busy_s = sum(busy_each) / len(busy_each)
    if busy_s <= 0:
        return None
    idle = attribute_gaps(sorted(gaps_all), trace.host_spans, lo, hi)
    n_dev = len(trace.device_ops)
    return {
        "busy_s": busy_s,
        "window_s": hi - lo,
        "device_ops": [[name, sec / n_dev] for name, sec in ops.most_common(top)],
        "idle_gaps": sorted(([label, sec / n_dev] for label, sec in idle.items()),
                            key=lambda p: -p[1])[:top],
    }


def read_xspace(path: str) -> Trace:
    """The TPU op events and the benchmark's host annotations of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name[len(PREFIX):], e.start_ns * 1e-9,
                                     e.end_ns * 1e-9))
    return Trace(device_ops=device_ops, host_spans=host)
