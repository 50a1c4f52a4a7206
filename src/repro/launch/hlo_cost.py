"""Trip-count-aware cost analysis of compiled XLA modules.

XLA's built-in ``cost_analysis()`` counts while-loop bodies ONCE (verified:
a 10-iteration scan of a matmul reports 1 matmul of FLOPs), which silently
underreports every scanned program (the sweep's round loop and chunk scans).
Unrolling for analysis is exact but compiles orders of magnitude slower. This
module
instead walks the *optimized HLO text* structurally:

* computations are parsed into instruction lists (result type, op, operands,
  metadata);
* a call graph is built (while -> body/cond, fusion -> calls, call/conditional
  -> callees);
* while trip counts are recovered from the loop condition (the ``compare``
  against a constant — exact for lax.scan-lowered loops);
* FLOPs: dot/convolution ops contribute 2 * prod(result) * prod(contracting)
  (contracting size = prod(lhs)/prod(batch+lhs-kept)); elementwise transcend-
  entals counted separately;
* bytes: every top-level instruction contributes operand bytes + result bytes
  (fusions count at their call site — operands + outputs, matching streaming
  execution, not their internals);
* collectives: wire bytes via ring formulas, scaled by trip counts.

Validated against ``cost_analysis()`` on unrolled programs (see
tests/test_hlo_cost.py).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional

from repro.launch import hlo_text
from repro.launch.hlo_text import ring_wire_bytes

_DTYPE_BYTES = hlo_text.DTYPE_BYTES
_SHAPE_RE = hlo_text.SHAPE_RE
_GROUPS_RE = hlo_text.GROUPS_RE
_GROUPS_IOTA_RE = hlo_text.GROUPS_IOTA_RE

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\([^)]*\)|[\w\[\],\{\}\s/]*?)\s*"
    r"([\w\-]+)\((.*)$")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")

_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "all-gather-start", "all-reduce-start",
                "collective-permute-start", "reduce-scatter-start",
                "all-to-all-start"}

_TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "power",
                   "logistic", "sine", "cosine", "exponential-minus-one",
                   "log-plus-one", "atan2", "cbrt", "erf"}


# from launch/hlo_text.py; the local names stay because tests and this
# module's walker address them directly
_shape_list = hlo_text.shape_list
_type_bytes = hlo_text.type_bytes


@dataclasses.dataclass
class Instr:
    name: str
    rtype: str
    op: str
    rest: str                 # operand list + attributes (raw)
    operands: List[str]


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    by_name: Dict[str, Instr]


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s:
            continue
        if not line.startswith(" ") and ("->" in s) and s.endswith("{"):
            m = _COMP_HDR_RE.match(s)
            if m:
                cur = Computation(m.group(1), [], {})
                comps[cur.name] = cur
            continue
        if s == "}":
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, op, rest = m.groups()
        operands = re.findall(r"%([\w\.\-]+)", rest.split("metadata=")[0])
        cur.instrs.append(Instr(name, rtype, op, rest, operands))
        cur.by_name[name] = cur.instrs[-1]
    return comps


def _group_size(rest: str) -> int:
    return hlo_text.group_size(rest, default=2)


def _operand_type(comp: Computation, comps: Dict[str, Computation],
                  op_name: str) -> str:
    ins = comp.by_name.get(op_name)
    return ins.rtype if ins else ""


def _dot_flops(comp: Computation, ins: Instr) -> float:
    shapes = _shape_list(ins.rtype)
    if not shapes:
        return 0.0
    out_elems = math.prod(shapes[0][1]) if shapes[0][1] else 1
    # contracting size from lhs operand type and contracting dims
    lhs_t = _operand_type(comp, {}, ins.operands[0]) if ins.operands else ""
    c = _CONTRACT_RE.search(ins.rest)
    if lhs_t and c is not None:
        lhs_shapes = _shape_list(lhs_t)
        if lhs_shapes:
            lhs_shape = lhs_shapes[0][1]
            cd = [int(x) for x in c.group(1).split(",") if x.strip()]
            k = math.prod(lhs_shape[d] for d in cd) if cd else 1
            return 2.0 * out_elems * k
    return 2.0 * out_elems   # fallback: unknown contraction


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    transcendentals: float = 0.0
    coll_wire_bytes: float = 0.0
    coll_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.transcendentals += o.transcendentals
        self.coll_wire_bytes += o.coll_wire_bytes
        for k, v in o.coll_by_kind.items():
            self.coll_by_kind[k] = self.coll_by_kind.get(k, 0.0) + v
        return self

    def scaled(self, f: float) -> "Cost":
        return Cost(self.flops * f, self.bytes * f, self.transcendentals * f,
                    self.coll_wire_bytes * f,
                    {k: v * f for k, v in self.coll_by_kind.items()})


def _trip_count(cond: Computation) -> int:
    """Recover a scan/while trip count from its condition computation: the
    constant compared against the induction variable."""
    consts = {}
    for ins in cond.instrs:
        if ins.op == "constant" and ("s32" in ins.rtype or "s64" in ins.rtype):
            # rest looks like "10)" (the opening paren was consumed by the
            # instruction regex)
            m = re.match(r"\(?(-?\d+)\)", ins.rest)
            if m:
                consts[ins.name] = int(m.group(1))
    for ins in cond.instrs:
        if ins.op in ("compare", "fusion") or "compare" in ins.rest:
            for op_name in ins.operands:
                if op_name in consts and consts[op_name] > 0:
                    return consts[op_name]
    pos = [v for v in consts.values() if v > 0]
    return max(pos) if pos else 1


def _comp_cost(comp: Computation, comps: Dict[str, Computation],
               memo: Dict[str, Cost], as_fusion: bool = False) -> Cost:
    """Cost of one computation, recursing into callees. Fusion computations
    contribute dot/transcendental flops but not per-instruction bytes."""
    key = comp.name + ("#f" if as_fusion else "")
    if key in memo:
        return memo[key]
    total = Cost()
    memo[key] = total   # guard cycles
    for ins in comp.instrs:
        op = ins.op
        if op == "while":
            body_name = _BODY_RE.search(ins.rest)
            cond_name = _COND_RE.search(ins.rest)
            if body_name and body_name.group(1) in comps:
                body = comps[body_name.group(1)]
                trips = 0
                m = re.search(r'known_trip_count[":{\s]+n["\s:]+"?(\d+)',
                              ins.rest)
                if m:
                    trips = int(m.group(1))
                if trips <= 0 and cond_name and cond_name.group(1) in comps:
                    trips = _trip_count(comps[cond_name.group(1)])
                total += _comp_cost(body, comps, memo).scaled(max(trips, 1))
            continue
        if op in ("fusion",):
            m = _CALLS_RE.search(ins.rest)
            called = comps.get(m.group(1)) if m else None
            if called is not None:
                total += _comp_cost(called, comps, memo, as_fusion=True)
            # fusion I/O bytes at the call site; in-place slice-update /
            # slice-read fusions touch only the slice, not the whole buffer
            if not as_fusion:
                result_b = _type_bytes(ins.rtype)
                operand_b = [
                    _type_bytes(_operand_type(comp, comps, opn))
                    for opn in ins.operands]
                b = result_b + sum(operand_b)
                if called is not None:
                    dus = [i for i in called.instrs
                           if i.op == "dynamic-update-slice"]
                    dsl = [i for i in called.instrs
                           if i.op == "dynamic-slice"]
                    if dus:
                        slice_b = 0
                        for d in dus:
                            if len(d.operands) >= 2:
                                slice_b += _type_bytes(_operand_type(
                                    called, comps, d.operands[1]))
                        # drop buffer read+write, keep slice write+read
                        b = max(0, sum(operand_b) - result_b) + 2 * slice_b
                    elif dsl and operand_b:
                        # slice read: drop the big buffer operand
                        b = 2 * result_b + sum(operand_b) - max(operand_b)
                total += Cost(bytes=b)
            continue
        if op in ("call", "custom-call", "conditional", "async-start"):
            m = _CALLS_RE.search(ins.rest)
            if m and m.group(1) in comps:
                total += _comp_cost(comps[m.group(1)], comps, memo)
            mb = _BRANCHES_RE.search(ins.rest)
            if mb:
                branches = re.findall(r"%?([\w\.\-]+)", mb.group(1))
                if branches:
                    sub = [_comp_cost(comps[b], comps, memo)
                           for b in branches if b in comps]
                    if sub:   # conditional: worst-case branch
                        total += max(sub, key=lambda c: c.flops + c.bytes)
            continue
        base = op.replace("-start", "")
        if base in ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute"):
            nbytes = _type_bytes(ins.rtype)
            n = _group_size(ins.rest)
            wire = ring_wire_bytes(base, nbytes, n)
            c = Cost(coll_wire_bytes=wire, coll_by_kind={base: wire})
            c.bytes = 2.0 * nbytes
            total += c
            continue
        if op in ("dot", "convolution"):
            total += Cost(flops=_dot_flops(comp, ins))
        elif op in _TRANSCENDENTAL:
            n = 0
            for dt, shape in _shape_list(ins.rtype):
                n += math.prod(shape) if shape else 1
            total += Cost(transcendentals=float(n), flops=float(n))
        elif op in ("add", "multiply", "subtract", "divide", "maximum",
                    "minimum", "compare", "select", "and", "or", "xor",
                    "negate", "abs", "floor", "ceil", "round-nearest-afz"):
            n = 0
            for dt, shape in _shape_list(ins.rtype):
                n += math.prod(shape) if shape else 1
            total += Cost(flops=float(n))
        if not as_fusion and op not in ("parameter", "constant",
                                        "get-tuple-element", "tuple",
                                        "bitcast"):
            if op == "dynamic-update-slice":
                b = 2 * _type_bytes(_operand_type(comp, comps,
                                                  ins.operands[1])
                                    if len(ins.operands) > 1 else "")
            elif op == "dynamic-slice":
                b = 2 * _type_bytes(ins.rtype)
            else:
                b = _type_bytes(ins.rtype)
                for opn in ins.operands:
                    b += _type_bytes(_operand_type(comp, comps, opn))
            total += Cost(bytes=b)
    memo[key] = total
    return total


def analyze(hlo_text: str, entry: Optional[str] = None) -> Cost:
    comps = parse_module(hlo_text)
    if not comps:
        return Cost()
    # entry computation: the one marked ENTRY (we matched header without the
    # marker, so fall back to the largest top-level "main"-ish computation)
    entry_name = entry
    if entry_name is None:
        for line in hlo_text.splitlines():
            if line.startswith("ENTRY"):
                m = _COMP_HDR_RE.match(line.strip())
                if m:
                    entry_name = m.group(1)
                break
    if entry_name is None or entry_name not in comps:
        entry_name = max(comps, key=lambda c: len(comps[c].instrs))
    memo: Dict[str, Cost] = {}
    return _comp_cost(comps[entry_name], comps, memo)
