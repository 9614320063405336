"""The work one dispatch of an exact scan must do, from the configuration's
unpadded shape, and the least time the chip could do it in.

A dispatch of m queries against n rows of d float32 values reads the corpus
and the queries once and forms m * n dot products of length d:

    bytes = n * d * 4 + m * d * 4
    flops = 2 * m * n * d

This count holds whatever executor, padding or precision scheme serves the
dispatch, and it is a lower bound on any exact float32 scan: lane padding,
partial results written back and the extra passes of a float32 product on
a bf16 unit all cost more. The least time takes the bf16 peak, the most
generous one, so a share of it cannot pass 100% unless the time measured
leaves out part of the work.
"""
from __future__ import annotations


def scan_work(m: int, n: int, d: int) -> tuple[int, int]:
    """(bytes, flops) of one dispatch of m queries over n x d float32 rows."""
    return n * d * 4 + m * d * 4, 2 * m * n * d


def least_time_s(m: int, n: int, d: int, peaks: dict) -> tuple[float, str]:
    """(seconds, "bytes" | "flops"): the larger of the two bounds and which
    one it is."""
    nbytes, flops = scan_work(m, n, d)
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
