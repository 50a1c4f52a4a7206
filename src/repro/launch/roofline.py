"""Roofline-term derivation from compiled XLA artifacts.

This container has no TPU; the *compiled dry-run* is the profile. Per
(arch x shape x mesh) we derive three times (seconds, per step):

  T_comp = device_FLOPs / peak_flops
  T_mem  = device_bytes  / hbm_bw
  T_coll = device_wire_bytes / ici_bw

``compiled.cost_analysis()`` reports FLOPs / bytes for the *per-device* SPMD
program. Collective wire bytes are parsed from the optimized HLO text
(``compiled.as_text()``): for each all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute we take the result tensor sizes and convert to
per-device wire traffic with the standard ring formulas (x(n-1)/n, all-reduce
x2(n-1)/n) using the replica-group size parsed from the op.

Hardware parameters live in :class:`HardwareSpec`, one per JAX
``device_kind`` in :data:`HARDWARE`; the module-level ``PEAK_FLOPS`` /
``HBM_BW`` / ``ICI_BW`` constants are the TPU v5e row, kept for callers
that predate the table.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.launch.hlo_text import (
    COLLECTIVES as _COLLECTIVES,
    group_size,
    ring_wire_bytes,
    type_bytes as _tensor_bytes,
)


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline hardware parameters for one accelerator flavour.

    ``h2d_bw`` (host->device) and ``dispatch_us`` (per-launch overhead)
    exist for the plan tuner's cost model; the classic three-term roofline
    uses only the first three rates.
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

V5E = HARDWARE["TPU v5 lite"]
PEAK_FLOPS = V5E.peak_flops    # bf16 / chip
HBM_BW = V5E.hbm_bw            # bytes/s / chip
ICI_BW = V5E.ici_bw            # bytes/s / link


def _group_size(line: str) -> int:
    return group_size(line, default=2)


def _result_type(line: str) -> str:
    # "%name = TYPE op-name(...)" — everything between '=' and the op name
    lhs = line.split("=", 1)
    if len(lhs) != 2:
        return ""
    rhs = lhs[1]
    for op in _COLLECTIVES:
        idx = rhs.find(op)
        if idx > 0:
            return rhs[:idx]
    return ""


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    count: int = 0
    by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    by_kind_count: Dict[str, int] = dataclasses.field(default_factory=dict)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        kind = None
        for op in _COLLECTIVES:
            # match "op(" or "op-start(" to skip e.g. %all-reduce.3 operand refs
            if f" {op}(" in s or f" {op}-start(" in s:
                kind = op
                break
        if kind is None:
            continue
        rtype = _result_type(s.replace(f"{kind}-start", kind))
        nbytes = _tensor_bytes(rtype)
        if nbytes == 0:
            continue
        n = max(_group_size(s), 2)
        wire = ring_wire_bytes(kind, nbytes, n)
        stats.wire_bytes += wire
        stats.count += 1
        stats.by_kind[kind] = stats.by_kind.get(kind, 0.0) + wire
        stats.by_kind_count[kind] = stats.by_kind_count.get(kind, 0) + 1
    return stats


# HLO while-loops (scan over layer groups) report body cost ONCE; scale by
# trip count. We extract trip counts conservatively from known scan lengths.
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
    per_device_memory_bytes: Optional[float] = None
    model_flops: Optional[float] = None
    useful_flops_ratio: Optional[float] = None
    hardware: Optional[str] = None

    @property
    def t_step(self) -> float:
        """Optimistic step time: the binding roofline term (full overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self):
        return dataclasses.asdict(self)


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


def roofline(compiled, *, model_flops_per_device: Optional[float] = None,
             hlo_text: Optional[str] = None,
             structural: bool = True,
             hw: Optional[HardwareSpec] = None) -> RooflineTerms:
    """Derive the three terms. ``structural=True`` uses the trip-count-aware
    HLO walker (repro.launch.hlo_cost) — XLA's own cost_analysis counts
    while-loop bodies once, so scanned-layers programs need this.
    ``hw`` selects the hardware parameters (TPU v5e-like default)."""
    from repro.compat import compiled_cost_analysis
    from repro.launch import hlo_cost
    hw = hw if hw is not None else V5E
    ca = compiled_cost_analysis(compiled)
    text = hlo_text if hlo_text is not None else compiled.as_text()
    if structural:
        cost = hlo_cost.analyze(text)
        flops = cost.flops
        nbytes = cost.bytes
        coll = CollectiveStats(wire_bytes=cost.coll_wire_bytes,
                               count=0, by_kind=dict(cost.coll_by_kind))
    else:
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        coll = parse_collectives(text)
    out = terms_from_cost(flops, nbytes, coll.wire_bytes, hw,
                          collective_detail=coll.by_kind)
    try:
        ma = compiled.memory_analysis()
        out.per_device_memory_bytes = float(
            ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    out.model_flops = model_flops_per_device
    if model_flops_per_device and flops > 0:
        out.useful_flops_ratio = model_flops_per_device / flops
    return out


def model_flops_estimate(n_params_active: int, tokens: int) -> float:
    """The 6*N*D convention (fwd+bwd); callers pass fwd-only tokens/3 for
    inference shapes."""
    return 6.0 * float(n_params_active) * float(tokens)
