"""Roofline terms for the plan tuner's cost model.

Given per-device counters (FLOPs, HBM bytes, collective wire bytes — from
:mod:`repro.launch.hlo_cost`'s walk of a compiled program, or the tuner's
own count) we derive three times (seconds):

  T_comp = device_FLOPs / peak_flops
  T_mem  = device_bytes  / hbm_bw
  T_coll = device_wire_bytes / ici_bw

Hardware parameters live in :class:`HardwareSpec`, one per JAX
``device_kind`` in :data:`HARDWARE`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline hardware parameters for one accelerator flavour.

    ``h2d_bw`` (host->device) and ``dispatch_us`` (per-launch overhead)
    feed the tuner's overhead terms; the three-term roofline uses only the
    first three rates.
    """
    name: str
    peak_flops: float          # FLOP/s per chip (bf16 for TPUs)
    hbm_bw: float              # bytes/s per chip
    ici_bw: float              # bytes/s per link
    h2d_bw: float = 16e9       # host->device bytes/s (PCIe-ish default)
    dispatch_us: float = 3.0   # per kernel-launch overhead, microseconds

    @staticmethod
    def for_device_kind(device_kind: str) -> "HardwareSpec":
        """The table row for a JAX ``device_kind`` (``jax.devices()[0]
        .device_kind``). A kind the table does not know is an error, not a
        default: a peak assumed for the wrong chip makes every roofline
        share and cost ranking wrong."""
        try:
            return HARDWARE[device_kind]
        except KeyError:
            raise ValueError(
                f"no HardwareSpec for device_kind {device_kind!r}; known: "
                f"{sorted(HARDWARE)}. Add its published peaks to "
                "repro.launch.roofline.HARDWARE, with their source."
            ) from None


# Keyed by JAX's device_kind. Sources: Google Cloud TPU documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links: ~50 GB/s a link) and "TPU v4" (275 TFLOP/s
# bf16, 1,228 GB/s HBM).
HARDWARE: Dict[str, HardwareSpec] = {
    "TPU v5 lite": HardwareSpec("TPU v5 lite", 197e12, 819e9, 50e9,
                                h2d_bw=16e9),
    "TPU v4": HardwareSpec("TPU v4", 275e12, 1228e9, 100e9, h2d_bw=16e9),
    # not a measured device: a coarse single-socket stand-in so the plan
    # tuner can rank candidates on CPU test runs (measurement decides)
    "cpu": HardwareSpec("cpu", 0.5e12, 50e9, 50e9, h2d_bw=50e9,
                        dispatch_us=8.0),
}


@dataclasses.dataclass
class RooflineTerms:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    collective_detail: Dict[str, float]
    hardware: Optional[str] = None


def terms_from_cost(flops: float, nbytes: float, wire_bytes: float,
                    hw: HardwareSpec,
                    collective_detail: Optional[Dict[str, float]] = None,
                    ) -> RooflineTerms:
    """Roofline terms from already-extracted per-device counters."""
    t_c = flops / hw.peak_flops
    t_m = nbytes / hw.hbm_bw
    t_x = wire_bytes / hw.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    return RooflineTerms(
        flops_per_device=flops, bytes_per_device=nbytes,
        wire_bytes_per_device=wire_bytes,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        bottleneck=max(terms, key=terms.get),
        collective_detail=dict(collective_detail or {}), hardware=hw.name)
