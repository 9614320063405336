"""Published per-chip peaks for the roofline model, keyed by device kind.

``PEAKS`` maps the ``device_kind`` JAX reports (``jax.devices()[0]
.device_kind``) to the chip's published peaks; :func:`peaks_for` looks a
device up and refuses one that is not in the table, so no number is ever
computed against another chip's peaks. Source of the v5e row: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float  # per chip, FLOP/s
    hbm_bw: float  # per chip, B/s
    link_bw: float  # per ICI link, B/s
    hbm_bytes: int  # per chip capacity
    tdp_watts: float  # for the energy model in benchmarks


TPU_V5E = HwSpec(
    name="TPU v5 lite",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    link_bw=50e9,
    hbm_bytes=16 * 2**30,
    tdp_watts=170.0,  # board power estimate used by the energy proxy
)

#: device_kind -> published peaks. A TPU v5e reports "TPU v5 lite".
PEAKS = {TPU_V5E.name: TPU_V5E}


def peaks_for(device_kind: str) -> HwSpec:
    """The published peaks of `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a row to repro.roofline.hw.PEAKS"
        ) from None

# Reference devices from the paper's evaluation (energy model, Table 2/3)
ALVEO_U55C_WATTS = 150.0
XEON_E5_2683V4_WATTS = 120.0
A100_40G_WATTS = 400.0
