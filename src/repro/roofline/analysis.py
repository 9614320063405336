"""Three-term roofline from a compiled executable (CPU dry-run, TPU target).

    compute    = HLO_FLOPs / (chips * peak)
    memory     = HLO_bytes / (chips * hbm_bw)
    collective = wire_bytes / (chips * link_bw)

FLOPs/bytes come from compiled.cost_analysis(). Collective bytes are NOT in
cost_analysis: we parse the optimized HLO and sum ring-algorithm wire bytes
over every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (start variants counted once, done variants skipped).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Mapping

from repro.roofline.hw import HwSpec

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\(?[^=]*?\)?)\s*"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute|"
    r"ragged-all-to-all)\("
)
_GROUP_ITOA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUP_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUP_ITOA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes_per_device: float  # summed ring-model bytes on the busiest link path
    op_counts: dict
    op_bytes: dict

    def total_wire_bytes(self, chips: int) -> float:
        return self.wire_bytes_per_device * chips


def collective_bytes_from_hlo(hlo_text: str, default_group: int) -> CollectiveStats:
    """Ring-model per-device wire bytes summed over collective ops."""
    wire = 0.0
    counts: dict = {}
    op_bytes: dict = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        result_shape, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        g = _group_size(line, default_group)
        if g <= 1:
            continue
        out_b = _shape_bytes(result_shape)
        # async start ops return (operand, result) tuples: split heuristically
        if "-start" in m.group(2) and op in ("all-reduce", "collective-permute"):
            out_b //= 2
        if op == "all-gather":
            b = out_b * (g - 1) / g
        elif op == "reduce-scatter":
            b = out_b * (g - 1)  # operand = g * result
        elif op == "all-reduce":
            b = 2 * out_b * (g - 1) / g
        elif op in ("all-to-all", "ragged-all-to-all"):
            b = out_b * (g - 1) / g
        else:  # collective-permute
            b = out_b
        wire += b
        counts[op] = counts.get(op, 0) + 1
        op_bytes[op] = op_bytes.get(op, 0.0) + b
    return CollectiveStats(wire, counts, op_bytes)


@dataclasses.dataclass
class Roofline:
    chips: int
    hlo_flops: float
    hlo_bytes: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_flops_ratio: float
    collective_ops: dict
    step_time_s: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(
    hlo_flops: float,
    hlo_bytes: float,
    wire_per_device: float,
    chips: int,
    model_flops: float = 0.0,
    *,
    hw: HwSpec,
) -> Roofline:
    compute = hlo_flops / (chips * hw.peak_flops_bf16)
    memory = hlo_bytes / (chips * hw.hbm_bw)
    collective = wire_per_device / hw.link_bw  # == total/(chips*link_bw)
    terms = {"compute": compute, "memory": memory, "collective": collective}
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        chips=chips,
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        wire_bytes_per_device=wire_per_device,
        compute_s=compute,
        memory_s=memory,
        collective_s=collective,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / hlo_flops) if hlo_flops else 0.0,
        collective_ops={},
        step_time_s=max(terms.values()),
    )


def analyze_compiled(compiled, chips: int, model_flops: float, *,
                     hw: HwSpec) -> Roofline:
    """Full analysis of a jax compiled executable.

    cost_analysis() on the SPMD-partitioned module reports PER-DEVICE
    numbers (verified empirically: a (1024,512)x(512,512) matmul row-sharded
    4 ways reports 2mnk/4 flops). Global = per-device x chips, matching the
    brief's `HLO_FLOPs / (chips * peak)` convention.
    """
    cost: Mapping = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0)) * chips
    byts = float(cost.get("bytes accessed", 0.0)) * chips
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo, default_group=chips)
    r = roofline_terms(flops, byts, coll.wire_bytes_per_device, chips, model_flops, hw)
    r.collective_ops = {k: {"count": coll.op_counts[k], "bytes": coll.op_bytes[k]}
                        for k in coll.op_counts}
    return r
