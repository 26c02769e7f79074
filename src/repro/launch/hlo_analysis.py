"""Loop-aware cost analysis of compiled (partitioned) HLO text.

``compiled.cost_analysis()`` counts every ``while`` body ONCE — a 64-layer
scanned transformer under-reports FLOPs/bytes/collectives by ~64×. XLA does
record ``backend_config={"known_trip_count":{"n":...}}`` on each while after
optimization, so this module re-walks the HLO text with loop multipliers:

  flops        2 · out_elems · contracted_elems per ``dot`` (the MXU work;
               elementwise FLOPs are ignored, standard for MFU accounting),
               multiplied by the product of enclosing trip counts.
  bytes        HloCostAnalysis-style bytes-accessed: Σ (operand + result)
               bytes per materializing op at fusion granularity — fusion ops
               count their boundary buffers only, mirroring what a fused
               kernel actually reads/writes against HBM.
  collectives  operand bytes per all-reduce / all-gather / reduce-scatter /
               all-to-all / collective-permute, × multiplier — per-device
               traffic (shapes in the partitioned module are per-device).

All counts are per-device; multiply by mesh size for global totals.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "token": 0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# ops that move no data (views / metadata)
_FREE = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant",
         "iota", "after-all", "partition-id", "replica-id"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:[\w\[\],{}]+))\s+"
    r"([\w\-]+)\((.*)$")
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\((.*)\)\s+->")
_TRIP_RE = re.compile(r'known_trip_count[^}]*"n"\s*:\s*"(\d+)"')


def type_bytes(type_str: str, width_cap: int = 0) -> int:
    """Bytes of an HLO type string; ``width_cap`` (if >0) caps the per-element
    width — used to count collectives at the model's compute dtype, since the
    CPU stand-in backend legalizes bf16 collectives/dots to f32 (a TPU build
    moves them at bf16)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        width = _BYTES[dt]
        if width_cap and width > width_cap:
            width = width_cap
        total += n * width
    return total


def _shape_dims(type_str: str) -> Optional[List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str  # everything after the opening '('

    def operands(self) -> List[str]:
        depth, out, cur = 0, [], ""
        for ch in self.rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    out.append(cur)
                    break
                depth -= 1
            cur += ch
        args = "".join(out)
        return re.findall(r"%([\w.\-]+)", args)

    def attr(self, key: str) -> Optional[str]:
        m = re.search(rf"{key}=%([\w.\-]+)", self.rest)
        return m.group(1) if m else None

    def trip_count(self) -> int:
        m = _TRIP_RE.search(self.rest)
        return int(m.group(1)) if m else 1


@dataclasses.dataclass
class Computation:
    name: str
    params: Dict[str, str]          # param name -> type string
    instrs: List[Instr]

    def symbol(self, name: str) -> Optional[str]:
        if name in self.params:
            return self.params[name]
        for ins in self.instrs:
            if ins.name == name:
                return ins.type_str
        return None


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        if cur is None:
            h = _HEADER_RE.match(line)
            if h and line.rstrip().endswith("{"):
                params = {}
                for pm in re.finditer(r"([\w.\-]+)\s*:\s*((?:\([^)]*\))|[\w\[\],{}]+)",
                                      h.group(3)):
                    params[pm.group(1)] = pm.group(2)
                cur = Computation(h.group(2), params, [])
                if h.group(1):
                    entry_name = h.group(2)
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        im = _INSTR_RE.match(line)
        if im:
            cur.instrs.append(Instr(im.group(2), im.group(3), im.group(4),
                                    im.group(5)))
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _dot_flops(comp: Computation, ins: Instr) -> float:
    out_dims = _shape_dims(ins.type_str) or []
    out_elems = math.prod(out_dims) if out_dims else 1
    ops = ins.operands()
    contract = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
    if m and ops:
        lhs_type = comp.symbol(ops[0])
        lhs_dims = _shape_dims(lhs_type) if lhs_type else None
        if lhs_dims:
            for idx in m.group(1).split(","):
                if idx:
                    contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


def _conv_flops(comp: Computation, ins: Instr) -> float:
    """convolution: 2 · out_elems · (kernel spatial · in_channels)."""
    out_dims = _shape_dims(ins.type_str) or []
    out_elems = math.prod(out_dims) if out_dims else 1
    ops = ins.operands()
    if len(ops) < 2:
        return 0.0
    k_type = comp.symbol(ops[1])
    k_dims = _shape_dims(k_type) if k_type else None
    if not k_dims:
        return 0.0
    # kernel = spatial… x in_ch x out_ch (dnums vary; product/out_ch is robust)
    out_ch = k_dims[-1] if k_dims else 1
    return 2.0 * out_elems * (math.prod(k_dims) / max(out_ch, 1))


def _instr_bytes(comp: Computation, ins: Instr) -> float:
    """HloCostAnalysis-style bytes accessed for one materializing op.

    Slicing ops touch only the slice, not the whole operand (a dynamic-slice
    of one layer's weights inside a 64-iteration scan reads L× less than the
    stacked buffer); DUS updates in place.
    """
    op = ins.op
    out_b = type_bytes(ins.type_str)
    if op in ("dynamic-slice", "slice", "gather"):
        return 2.0 * out_b
    if op in ("dynamic-update-slice", "scatter"):
        ops_ = ins.operands()
        upd = type_bytes(comp.symbol(ops_[1]) or "") if len(ops_) > 1 else out_b
        return 2.0 * upd
    if op == "broadcast":
        ops_ = ins.operands()
        src = type_bytes(comp.symbol(ops_[0]) or "") if ops_ else 0
        return out_b + src
    return out_b + sum(type_bytes(comp.symbol(o) or "") for o in ins.operands())


def _fusion_bytes(comps: Dict[str, "Computation"], comp: Computation,
                  ins: Instr) -> float:
    """Boundary traffic of a fusion, modelling the TPU target:

    - an operand consumed *only through slice ops* counts as the sliced
      bytes, not the whole buffer (scan bodies slice stacked weights);
    - an operand that is the *target* of a dynamic-update-slice is updated
      in place: traffic = 2 × update bytes, and the fusion output (which
      aliases it) is not counted — this is how XLA buffer-assigns scan ys;
    - a pure dtype-conversion fusion costs nothing: those are the CPU
      stand-in backend's bf16→f32 legalization of loop carries, which does
      not exist on TPU where bf16 is compute-native.
    """
    callee = comps.get(ins.attr("calls") or "")
    operand_names = ins.operands()
    if callee is None:
        return float(type_bytes(ins.type_str)) + sum(
            type_bytes(comp.symbol(o) or "") for o in operand_names)
    ops_set = {u.op for u in callee.instrs}
    if ops_set <= {"convert", "parameter", "bitcast", "copy", "constant"}:
        return 0.0  # bf16 legalization artifact (see docstring)
    params = list(callee.params)
    by_name = {u.name: u for u in callee.instrs}

    def origin(name: str) -> str:
        """Walk back through dtype/layout no-ops to the originating value."""
        seen = set()
        while name in by_name and name not in seen:
            seen.add(name)
            u = by_name[name]
            if u.op in ("convert", "bitcast", "copy") and u.operands():
                name = u.operands()[0]
            else:
                break
        return name

    # uses of each param, looking through convert/bitcast/copy chains
    uses: Dict[str, List[Instr]] = {p: [] for p in params}
    for u in callee.instrs:
        if u.op in ("convert", "bitcast", "copy"):
            continue
        for o in u.operands():
            og = origin(o)
            if og in uses:
                uses[og].append(u)

    inplace: Dict[str, float] = {}
    aliased_output = False
    for u in callee.instrs:
        if u.op == "dynamic-update-slice":
            uops = u.operands()
            tgt = origin(uops[0]) if uops else ""
            if tgt in params:
                upd = (type_bytes(callee.symbol(uops[1]) or "")
                       if len(uops) > 1 else 0)
                inplace[tgt] = 2.0 * upd
                aliased_output = True
    total = 0.0 if aliased_output else float(type_bytes(ins.type_str))
    for i, oname in enumerate(operand_names):
        if i >= len(params):
            total += type_bytes(comp.symbol(oname) or "")
            continue
        pname = params[i]
        if pname in inplace:
            total += inplace[pname]
            continue
        puses = uses.get(pname, [])
        if puses and all(u.op in ("dynamic-slice", "slice") for u in puses):
            total += sum(type_bytes(u.type_str) for u in puses)
            continue
        total += type_bytes(comp.symbol(oname) or "")
    return total


def _is_carry_copy(comp: Computation, ins: Instr) -> bool:
    """A ``copy`` (inside a loop body) whose source resolves to a loop
    parameter: XLA-CPU copy-insertion double-buffering the carried state.
    The TPU buffer assigner aliases the carry in place (standard decode-loop
    behaviour), so these bytes are tracked separately, not as HBM traffic."""
    by_name = {u.name: u for u in comp.instrs}
    name = ins.operands()[0] if ins.operands() else ""
    seen = set()
    while name in by_name and name not in seen:
        seen.add(name)
        u = by_name[name]
        if u.op in ("convert", "bitcast", "copy", "get-tuple-element") and u.operands():
            name = u.operands()[0]
        elif u.op == "parameter":
            return True
        else:
            return False
    return name in comp.params


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    carry_copy_bytes: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    collective_count: int = 0
    unknown_trip_whiles: int = 0

    @property
    def collective_total(self) -> float:
        return sum(self.collective_bytes.values())


def analyze(text: str, collective_width_cap: int = 0) -> Cost:
    comps = parse_module(text)
    cost = Cost()
    if "__entry__" not in comps:
        return cost
    # (computation, multiplier, fusion_context, loop_body)
    stack: List[Tuple[str, float, bool, bool]] = [
        (comps["__entry__"].name, 1.0, False, False)]
    seen_guard = 0
    while stack:
        cname, mult, in_fusion, in_loop = stack.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        seen_guard += 1
        if seen_guard > 100_000:  # malformed module safety valve
            break
        for ins in comp.instrs:
            op = ins.op
            if op == "while":
                trip = ins.trip_count()
                if trip == 1 and "known_trip_count" not in ins.rest:
                    cost.unknown_trip_whiles += 1
                body, cond = ins.attr("body"), ins.attr("condition")
                if body:
                    stack.append((body, mult * trip, in_fusion, True))
                if cond:
                    stack.append((cond, mult * trip, in_fusion, True))
                continue
            if op == "fusion":
                callee = ins.attr("calls")
                if callee:
                    stack.append((callee, mult, True, in_loop))
                if not in_fusion:
                    cost.bytes_accessed += mult * _fusion_bytes(comps, comp, ins)
                continue
            if op == "conditional" or op == "call":
                for key in ("true_computation", "false_computation",
                            "branch_computations", "to_apply"):
                    callee = ins.attr(key)
                    if callee:
                        stack.append((callee, mult, in_fusion, in_loop))
                continue
            if op == "dot":
                cost.flops += mult * _dot_flops(comp, ins)
            elif op == "convolution":
                cost.flops += mult * _conv_flops(comp, ins)
            if op in COLLECTIVES:
                cap = collective_width_cap
                if op in ("all-gather", "all-reduce", "collective-permute"):
                    # traffic each device receives == the result
                    b = type_bytes(ins.type_str, cap)
                else:  # reduce-scatter / all-to-all: what each device sends
                    b = sum(type_bytes(comp.symbol(o) or "", cap)
                            for o in ins.operands())
                    if b == 0:
                        b = type_bytes(ins.type_str, cap)
                cost.collective_bytes[op] += mult * b
                cost.collective_count += 1
            if not in_fusion and op not in _FREE:
                b = mult * _instr_bytes(comp, ins)
                if op == "copy" and in_loop and _is_carry_copy(comp, ins):
                    cost.carry_copy_bytes += b
                else:
                    cost.bytes_accessed += b
    return cost


def peak_aval_bytes(fn, *args, **kwargs) -> Tuple[int, str]:
    """Largest single intermediate array (bytes) anywhere in ``fn``'s jaxpr.

    Recurses through every sub-jaxpr an equation carries (pjit bodies,
    shard_map bodies, scan/while/cond branches, pallas grids), so values
    inside a ``shard_map`` are counted at their PER-DEVICE shapes — which is
    exactly what the distributed-factor bench needs to assert that no shard
    ever materializes the full (d, d) system: the gather-then-factor
    collective shows a (d, d) transient here, the tile-parallel path tops
    out at its (d/shards, d) row tile. A static upper bound on per-device
    live bytes, not a simulation of XLA's buffer assignment (rematerialization
    can only shrink it). Returns ``(bytes, shape_str)`` for the peak value.
    """
    import jax
    import jax.extend.core as core
    import numpy as np

    def aval_bytes(v):
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            return 0, ""
        n = int(np.prod(aval.shape)) if aval.shape else 1
        return n * np.dtype(aval.dtype).itemsize, str(aval)

    def is_jaxpr(x):
        return isinstance(x, (core.Jaxpr, core.ClosedJaxpr))

    def walk(jaxpr):
        if isinstance(jaxpr, core.ClosedJaxpr):
            jaxpr = jaxpr.jaxpr
        # equation outputs only: the caller's (sharded, resident) inputs are
        # not transients of the solve
        best = (0, "")
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                best = max(best, aval_bytes(v))
            for sub in jax.tree_util.tree_leaves(
                    eqn.params, is_leaf=is_jaxpr):
                if is_jaxpr(sub):
                    best = max(best, walk(sub))
        return best

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return walk(closed)
