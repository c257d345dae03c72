"""Reduce a profiler trace of the measured window to device busy time,
idle gaps and the operations that took the most time.

``events(path)`` reads an ``.xplane.pb`` into plain lists, and
``reduce(...)`` works on those lists only, so it can be checked on a
constructed trace. Device planes are ``/device:TPU:<i>``; busy time is
the union of the intervals of the ``XLA Ops`` line, clipped to the
window that the harness's ``bench_window`` host span marks.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("device_put", "dispatch", "wait")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def events(path: str) -> dict:
    """{"devices": {id: [(name, start_ns, dur_ns), ...]},
        "modules": {id: [(program, start_ns, dur_ns), ...]},
        "host": [(name, start_ns, dur_ns), ...]} (host: the harness's
    spans only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "modules": {}, "host": []}
    keep = {WINDOW_SPAN, *HOST_SPANS}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                key = "devices" if line.name == OPS_LINE else "modules"
                out[key][int(m.group(1))] = [
                    (e.name, e.start_ns, e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events if e.name in keep]
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def window(ev: dict) -> tuple:
    spans = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def instruction(op: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    m = re.match(r"%?([\w.\-]+)", op)
    return m.group(1) if m else op


def program(module: str) -> str:
    """``jit_bwd(1079696708650784)`` -> ``jit_bwd``."""
    return re.sub(r"\(\d+\)$", "", module)


def _enclosing(modules: list):
    """start_ns -> name of the program running then on that device."""
    spans = sorted((s, s + d, program(n)) for n, s, d in modules)
    starts = [s for s, _, _ in spans]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else None
    return find


def _self_times(ops: list, w0: int, w1: int):
    """(name, start, a, b, own_ns) for each op that overlaps [w0, w1):
    [a, b) is its interval clipped to the window, ``own_ns`` that
    interval less the clipped intervals of the ops nested directly in it
    (a loop's body runs inside the loop's own event). Ops that only
    overlap count in full."""
    rows = []
    stack = []                          # indices of open enclosing ops
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        a, b = max(s, w0), min(s + d, w1)
        while stack and rows[stack[-1]][5] < s + d:
            stack.pop()                 # ended, or only overlaps this op
        row = [name, s, a, b, max(b - a, 0), s + d]
        if stack and b > a:
            rows[stack[-1]][4] -= b - a
        stack.append(len(rows))
        rows.append(row)
    return [(n, s, a, b, max(own, 0)) for n, s, a, b, own, _ in rows
            if b > a]


def reduce(ev: dict, device_ids, op_labels: dict | None = None,
           top: int = 10) -> dict:
    """Busy seconds per device (union of op intervals inside the window),
    the window's length, the ``top`` operation groups by device seconds
    summed over the devices, and the ``top`` longest idle gaps, each
    named by the harness span the host was in at the gap's middle.

    An operation is grouped by its JAX name stack where ``op_labels``
    knows it. Without labels, on several devices, it is grouped by the
    program it ran in and the device (a pipeline's stage programs);
    otherwise by its instruction name without the numeric suffix."""
    w0, w1 = window(ev)
    by_program = not op_labels and len(device_ids) > 1
    op_labels = op_labels or {}
    host = sorted((s, s + d, n) for n, s, d in ev["host"] if n in HOST_SPANS)
    busy, groups, gaps = {}, {}, []
    for dev in device_ids:
        ivs = []
        prog = _enclosing(ev.get("modules", {}).get(dev, []))
        for name, s, a, b, own in _self_times(ev["devices"].get(dev, []),
                                              w0, w1):
            ivs.append((a, b))
            ins = instruction(name)
            label = op_labels.get(ins)
            if label is None and by_program and prog(s):
                label = f"{prog(s)} (device {dev})"
            label = label or re.sub(r"\.\d+$", "", ins)
            groups[label] = groups.get(label, 0.0) + own / 1e9
        merged = _union(ivs)
        busy[dev] = sum(b - a for a, b in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2], strict=True):
            if b > a:
                mid = (a + b) / 2
                what = next((n for s, e, n in host if s <= mid < e),
                            "between spans")
                gaps.append((f"{what} (device {dev})", (b - a) / 1e9))
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy,
        "device_ops": sorted(groups.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda kv: -kv[1])[:top],
    }


_TRANSFORMS = ("jit(", "pjit(", "jvp(", "transpose(", "checkpoint", "remat",
               "rematted_computation", "closed_call", "while", "body", "cond")


def hlo_op_labels(hlo_text: str, depth: int = 3) -> dict:
    """Instruction name -> a label from the compiled program's metadata:
    the pass (``fwd``, ``recompute`` for a rematerialised forward inside
    the backward, ``bwd``, or ``step`` outside the gradient) and the last
    ``depth`` parts of the ``op_name`` path without ``jit(...)``,
    transform and loop markers: the program's own named scopes, if any,
    and the primitive."""
    out = {}
    pat = re.compile(r"%([\w.\-]+) = .*?metadata=\{[^}]*op_name=\"([^\"]+)\"")
    for ins, op_name in pat.findall(hlo_text):
        parts = op_name.split("/")
        if "rematted_computation" in parts:
            phase = "recompute"
        elif any(p.startswith("transpose(") for p in parts):
            phase = "bwd"
        elif any(p.startswith("jvp(") for p in parts):
            phase = "fwd"
        else:
            phase = "step"
        keep = [p for p in parts if p and not p.startswith(_TRANSFORMS)]
        out[ins] = f"{phase} " + ("/".join(keep[-depth:]) or op_name)
    return out
