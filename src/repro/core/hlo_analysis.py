"""Trip-count-aware analyzer for optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts each while-loop (lax.scan) body
ONCE, which under-reports FLOPs/bytes for scan-over-layers models by the
trip count. This module re-derives the three roofline inputs directly from
``compiled.as_text()``:

  * flops           — dot / convolution ops, multiplied through the call
                      graph by every enclosing while's known_trip_count
  * hbm bytes       — per top-level op: operand + result bytes, with
                      fusions counted at their boundary only (a fusion is
                      one kernel: internal traffic stays in registers/VMEM)
  * collective wire bytes — per collective opcode, with ring-algorithm
                      factors (all-reduce 2x, others 1x of the result size)

This is the profiler the §Perf hillclimb reads; it is validated against
cost_analysis on loop-free modules in tests.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "after-all", "partition-id", "replica-id", "iota"}


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` (a dict), empty when XLA reports none."""
    return compiled.cost_analysis() or {}


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str):
    """Dims of the FIRST array shape in a type string."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return ()
    return tuple(int(d) for d in m.group(2).split(",") if d)


@dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    operands: list
    attrs: str

    def attr_list(self, key: str):
        m = re.search(rf"{key}={{([0-9,]*)}}", self.attrs)
        if not m:
            return []
        return [int(x) for x in m.group(1).split(",") if x]

    def called(self, key: str):
        m = re.search(rf"{key}=(%[\w.\-]+)", self.attrs)
        return m.group(1) if m else None

    @property
    def trip_count(self):
        m = re.search(r'"known_trip_count":{"n":"(\d+)"}', self.attrs)
        return int(m.group(1)) if m else None


@dataclass
class Computation:
    name: str
    is_entry: bool
    param_types: dict = field(default_factory=dict)
    ops: list = field(default_factory=list)
    types: dict = field(default_factory=dict)  # op/param name -> type str


_COMP_HDR = re.compile(
    r"^(ENTRY )?(%[\w.\-]+)\s*\((.*)\)\s*->\s*(.*?)\s*\{\s*$")
_OP_START = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")
_OPCODE_RE = re.compile(r"\s*([a-z][\w\-]*)\(")
_PARAM_RE = re.compile(r"(%?[\w.\-]+):\s*((?:\([^)]*\))|[a-z][a-z0-9]*\[[0-9,]*\])")


def parse_hlo(text: str) -> dict:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        h = _COMP_HDR.match(line)
        if h:
            cur = Computation(name=h.group(2), is_entry=bool(h.group(1)))
            for pm in _PARAM_RE.finditer(h.group(3)):
                pname = pm.group(1)
                if not pname.startswith("%"):
                    pname = "%" + pname
                cur.param_types[pname] = pm.group(2)
                cur.types[pname] = pm.group(2)
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _OP_START.match(line)
        if not m:
            continue
        name = m.group(1)
        rest = line[m.end():]
        # type: either a balanced "(tuple, ...)" (may contain /*index=k*/
        # comments) or a single "dtype[dims]{layout}" token
        if rest.startswith("("):
            depth, i = 0, 0
            while i < len(rest):
                if rest[i] == "(":
                    depth += 1
                elif rest[i] == ")":
                    depth -= 1
                    if depth == 0:
                        i += 1
                        break
                i += 1
            type_str, rest = rest[:i], rest[i:]
        else:
            tm = re.match(r"[a-z][a-z0-9]*\[[0-9,]*\](?:{[^}]*})?", rest)
            if not tm:
                continue
            type_str, rest = tm.group(0), rest[tm.end():]
        om = _OPCODE_RE.match(rest)
        if not om:
            continue
        opcode = om.group(1)
        rest = rest[om.end():]
        # operand list: up to the matching close paren
        depth, i = 1, 0
        while i < len(rest) and depth:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        operand_str, attrs = rest[:i - 1], rest[i:]
        operands = re.findall(r"%[\w.\-]+", operand_str)
        op = Op(name, type_str, opcode, operands, attrs)
        cur.ops.append(op)
        cur.types[name] = type_str
    return comps


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = 1
    for d in _shape_dims(op.type_str):
        out_elems *= d
    lhs_type = comp.types.get(op.operands[0], "") if op.operands else ""
    lhs_dims = _shape_dims(lhs_type)
    k = 1
    for i in op.attr_list("lhs_contracting_dims"):
        if i < len(lhs_dims):
            k *= lhs_dims[i]
    return 2.0 * out_elems * k


def _conv_flops(op: Op, comp: Computation) -> float:
    out_elems = 1
    for d in _shape_dims(op.type_str):
        out_elems *= d
    rhs_type = comp.types.get(op.operands[1], "") if len(op.operands) > 1 else ""
    rhs_dims = _shape_dims(rhs_type)
    k = 1
    for d in rhs_dims[:-1]:  # kernel spatial x in-channels (approx)
        k *= d
    return 2.0 * out_elems * k


@dataclass
class HloStats:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_wire_bytes: float = 0.0
    collective_bytes: dict = field(default_factory=lambda: defaultdict(float))
    collective_counts: dict = field(default_factory=lambda: defaultdict(float))
    transcendental_elems: float = 0.0
    while_trips: list = field(default_factory=list)
    # per-op attribution for the perf loop: opcode -> (bytes, flops, count)
    by_opcode: dict = field(default_factory=lambda: defaultdict(
        lambda: [0.0, 0.0, 0.0]))
    top_ops: list = field(default_factory=list)   # (bytes, name, opcode)

    def record(self, name, opcode, nbytes, nflops, mult):
        e = self.by_opcode[opcode]
        e[0] += nbytes
        e[1] += nflops
        e[2] += mult
        if nbytes > 0:
            self.top_ops.append((nbytes, name, opcode))
            if len(self.top_ops) > 4096:
                self.top_ops.sort(reverse=True)
                del self.top_ops[512:]

    def summary(self, k: int = 15) -> str:
        lines = [f"flops={self.flops:.3e} bytes={self.bytes_accessed:.3e} "
                 f"coll={self.collective_wire_bytes:.3e}"]
        lines.append("-- by opcode (bytes desc) --")
        for oc, (b, f, c) in sorted(self.by_opcode.items(),
                                    key=lambda kv: -kv[1][0])[:k]:
            lines.append(f"  {oc:28s} bytes={b:.3e} flops={f:.3e} n={c:.0f}")
        lines.append("-- top ops by bytes --")
        for b, name, oc in sorted(self.top_ops, reverse=True)[:k]:
            lines.append(f"  {b:.3e}  {oc:20s} {name}")
        return "\n".join(lines)

    def as_dict(self):
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_wire_bytes": self.collective_wire_bytes,
            "collective_bytes": dict(self.collective_bytes),
            "collective_counts": dict(self.collective_counts),
            "while_trips": self.while_trips,
        }


_TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "power",
                   "logistic", "sine", "cosine", "divide"}


def _op_bytes(op: Op, comp: Computation, oc: str) -> float:
    """HBM traffic model for one top-level op (TPU-oriented):

    * dynamic-update-slice updates in place — traffic is 2x the update
      slice, NOT the carried buffer (XLA aliases the input buffer);
    * dynamic-slice / gather read+write the slice/result only;
    * plain copies of a loop-carried buffer are CPU-lowering artifacts —
      TPU aliases the carry; charge one write;
    * everything else: operands read + result written.
    """
    res = _type_bytes(op.type_str)
    opnds = [_type_bytes(comp.types.get(o, "")) for o in op.operands]
    if oc == "dynamic-update-slice":
        upd = opnds[1] if len(opnds) > 1 else 0
        return 2.0 * upd
    if oc in ("dynamic-slice", "gather"):
        return 2.0 * res
    if oc in ("copy", "bitcast-convert", "transpose") and opnds \
            and max(opnds) == res:
        return float(res)
    return float(res + sum(opnds))


def _fusion_bytes(op: Op, comp: Computation) -> float:
    """Fusion boundary traffic with in-place-update correction: when the
    fusion both consumes and produces the same-size (large) buffer and its
    name marks a dynamic-update-slice or pure copy, the buffer pass-through
    is aliased, so only the true update traffic is charged."""
    res = _type_bytes(op.type_str)
    opnds = [_type_bytes(comp.types.get(o, "")) for o in op.operands]
    total = res + sum(opnds)
    name = op.name
    if "scatter" in name:
        big = max(opnds, default=0)
        if big and abs(big - res) <= 0.01 * max(big, res):
            # scatter updates in place: traffic = indices + updates (r/w)
            small = sum(opnds) - big
            return float(2.0 * small) if small > 0 else float(res)
    if "dynamic-update-slice" in name:
        big = max(opnds, default=0)
        if big and abs(big - res) <= 0.01 * max(big, res):
            # charge: remaining operands (the update) read + written once
            small = sum(opnds) - big
            return float(2.0 * small) if small > 0 else float(res)
    if name.startswith(("%copy_bitcast", "%bitcast_copy", "%copy_fusion")) \
            and opnds and abs(sum(opnds) - res) <= 0.01 * max(res, 1):
        # pure copy of loop-carried buffers (possibly a tuple of them):
        # TPU aliases the carry; charge one write
        return float(res)
    return float(total)


def _walk(comp: Computation, comps: dict, mult: float, stats: HloStats,
          flops_only: bool, _seen_depth: int = 0):
    if _seen_depth > 64:
        return
    for op in comp.ops:
        oc = op.opcode
        if oc == "while":
            trips = op.trip_count or 1
            stats.while_trips.append(trips)
            body = op.called("body")
            cond = op.called("condition")
            for c in (body, cond):
                if c and c in comps:
                    _walk(comps[c], comps, mult * trips, stats, flops_only,
                          _seen_depth + 1)
            continue
        if oc in ("call", "conditional", "async-start"):
            for key in ("to_apply", "true_computation", "false_computation",
                        "branch_computations", "called_computation"):
                c = op.called(key)
                if c and c in comps:
                    _walk(comps[c], comps, mult, stats, flops_only,
                          _seen_depth + 1)
            if oc == "conditional":
                continue
        if oc == "fusion":
            c = op.called("calls")
            f_before = stats.flops
            if c and c in comps:
                _walk(comps[c], comps, mult, stats, True, _seen_depth + 1)
            if not flops_only:
                b = _fusion_bytes(op, comp)
                stats.bytes_accessed += mult * b
                stats.record(op.name, "fusion", mult * b,
                             stats.flops - f_before, mult)
            continue
        if oc == "dot":
            stats.flops += mult * _dot_flops(op, comp)
        elif oc == "convolution":
            stats.flops += mult * _conv_flops(op, comp)
        elif oc in _TRANSCENDENTAL:
            n = 1
            for d in _shape_dims(op.type_str):
                n *= d
            stats.transcendental_elems += mult * n
        if oc in COLLECTIVES:
            b = _type_bytes(op.type_str)
            stats.collective_bytes[oc] += mult * b
            stats.collective_counts[oc] += mult
            stats.collective_wire_bytes += mult * b * _WIRE_FACTOR[oc]
        if not flops_only and oc not in _SKIP_BYTES:
            b = _op_bytes(op, comp, oc)
            stats.bytes_accessed += mult * b
            nflops = mult * _dot_flops(op, comp) if oc == "dot" else 0.0
            stats.record(op.name, oc, mult * b, nflops, mult)


def analyze_hlo(text: str) -> HloStats:
    comps = parse_hlo(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    stats = HloStats()
    if entry is None:
        return stats
    _walk(entry, comps, 1.0, stats, flops_only=False)
    return stats


def analyze_compiled(compiled) -> HloStats:
    return analyze_hlo(compiled.as_text())
