"""HLO-text parsing primitives for ``launch/hlo_cost.py``'s structural,
trip-count-aware walker: the dtype-size table, the shape/replica-group
regexes and the ring-formula collective wire-byte model.

Ring formulas (per-device wire traffic for a group of size ``n``):

  all-reduce          2 * b * (n-1) / n     (reduce-scatter + all-gather)
  all-gather          b * (n-1) / n         (b = gathered result)
  reduce-scatter      b * (n-1)             (b = scattered shard)
  all-to-all          b * (n-1) / n
  collective-permute  b                     (one neighbour hop)
"""
from __future__ import annotations

import math
import re
from typing import List, Tuple

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

# one shaped result:  f32[256,1024]{1,0}   (layout braces optional)
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# replica_groups={{0,1},{2,3}} (nested) or ={0,1} (flat): first group
GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,\s]+)\}")
# e.g. replica_groups=[32,16]<=[16,32]T(1,0) — iota form: groups x size
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

def shape_list(type_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All (dtype, shape) pairs in an HLO type string (tuples flatten)."""
    out = []
    for dt, dims in SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((dt, shape))
    return out


def type_bytes(type_str: str) -> int:
    """Total bytes of all tensors in an HLO type string."""
    total = 0
    for dt, shape in shape_list(type_str):
        total += DTYPE_BYTES[dt] * (math.prod(shape) if shape else 1)
    return total


def group_size(attrs: str, default: int = 2) -> int:
    """Replica-group size from an instruction's attribute text.

    ``default`` is the conservative fallback when groups are implicit.
    """
    m = GROUPS_IOTA_RE.search(attrs)
    if m:
        return max(int(m.group(2)), 1)
    m = GROUPS_RE.search(attrs)
    if m:
        return max(len([x for x in m.group(1).split(",") if x.strip()]), 1)
    return default


def ring_wire_bytes(kind: str, nbytes: float, n: int) -> float:
    """Per-device wire bytes for one collective under the ring model."""
    if kind == "all-reduce":
        return 2.0 * nbytes * (n - 1) / n
    if kind == "all-gather":
        return nbytes * (n - 1) / n           # result = gathered
    if kind == "reduce-scatter":
        return nbytes * (n - 1)               # result = shard
    if kind == "all-to-all":
        return nbytes * (n - 1) / n
    return float(nbytes)                      # collective-permute
