"""The program's own spans and scopes, read from the profiler trace of the
measured window beside the harness's.

The program annotates its host side with ``repro.obs.spans`` spans, which
land in the trace on the device ops' clock while the profiler runs:
``pipeline.step``, one ``pipeline.F`` / ``pipeline.B`` / ``pipeline.W``
per schedule event, ``pipeline.transfer``, ``pipeline.sync`` around each
blocking device-to-host read, ``step.optimizer`` and others. A span that
dispatches one of the engine's programs names it in its ``program`` arg
and the devices it runs on in ``devices`` (``"0"``, ``"0,1"``); the
device trace's ``XLA Modules`` line shows the same program as
``jit_<program>``. Its device ops carry the program's named scopes
(``attention``, ``mlp``, ``head_ce``, ...) in the compiled program's
metadata, which ``scopes_of`` reads.

``events(path)`` reads an ``.xplane.pb`` into the lists ``bench.trace``
reads, plus the program's spans with their args; ``reduce(...)`` works
on those lists only, so it can be checked on a constructed trace. Busy
time and the window are ``bench.trace``'s, computed the same way.

The readers' context carries no trace, so ``locate()`` finds the one the
harness is reducing: the newest ``bench_trace_*`` directory it made in
the temporary directory, which it removes after the readers ran.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import tempfile

from bench import trace

PROGRAM_PREFIXES = ("pipeline.", "step.")
STEP_SPAN = "pipeline.step"
SYNC_SPAN = "pipeline.sync"
SCOPES = ("embed", "attention", "ssd", "mlp", "moe", "head_ce", "optimizer")
SHORT_GAP_NS = 10_000      # shorter gaps are summed, not named
SHORT_GAPS = "gaps under 10 us"


def locate() -> str | None:
    """The ``.xplane.pb`` of the trace the harness is reducing, if any."""
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            return trace.find_xplane(d)
        except FileNotFoundError:
            continue
    return None


def _is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


@functools.lru_cache(maxsize=2)
def events(path: str) -> dict:
    """``bench.trace.events(path)`` plus ``"spans"``: the program's host
    spans as ``(name, start_ns, dur_ns, args)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "modules": {}, "host": [], "spans": []}
    keep = {trace.WINDOW_SPAN, *trace.HOST_SPANS}
    for plane in pd.planes:
        m = trace._DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                key = "devices" if line.name == trace.OPS_LINE else "modules"
                out[key][int(m.group(1))] = [
                    (e.name, e.start_ns, e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in keep:
                        out["host"].append((e.name, e.start_ns, e.duration_ns))
                    elif _is_program_span(e.name):
                        out["spans"].append((e.name, e.start_ns, e.duration_ns,
                                             dict(e.stats)))
    return out


def _unwrap(part: str) -> str:
    """``transpose(jvp(head_ce))`` -> ``head_ce``: the scope a transform
    wraps in an op name."""
    m = re.fullmatch(r"\w+\((.*)\)", part)
    return _unwrap(m.group(1)) if m else part


def scopes_of(hlo_text: str) -> dict:
    """Instruction name -> the outermost of ``SCOPES`` in its ``op_name``
    metadata, for the instructions that have one."""
    out = {}
    pat = re.compile(r"%([\w.\-]+) = .*?metadata=\{[^}]*op_name=\"([^\"]+)\"")
    for ins, op_name in pat.findall(hlo_text):
        scope = next((p for p in map(_unwrap, op_name.split("/"))
                      if p in SCOPES), None)
        if scope is not None:
            out[ins] = scope
    return out


def step_hlo(cell, devices) -> str | None:
    """The compiled text of a one-chip cell's step, lowered from abstract
    arguments as ``SingleChip.op_labels`` lowers it from live ones (the
    persistent compile cache holds the program by then). None for other
    deployments.

    JAX's cache key leaves op metadata out, so an executable cached from
    the same program without its scopes (an older checkout's) comes back
    without. Where the lowered program has scopes that the compiled one
    does not show, the step is compiled again with the cache off: the
    compiler names the instructions the same way, so their scopes are
    those of the program that ran."""
    if cell.mix["deployment"] != "single":
        return None
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from bench import harness

    def lower():
        # a new jit each time: a jit keeps its lowering and executable
        system = harness.SingleChip(cell, devices)

        def abstract(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=system.rep), tree)
        params = jax.eval_shape(system.make_params, jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(system.opt.init, params)
        shape = (cell.mix["batch"], cell.mix["seq"])
        batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32) for k in ("tokens", "labels")}
        return system.fn.lower(abstract(params), abstract(opt_state),
                               jnp.asarray(0, jnp.int32), abstract(batch))
    lowered = lower()
    text = lowered.compile().as_text()
    if _scopes_in(lowered.as_text(debug_info=True)) <= set(scopes_of(text).values()):
        return text
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()      # JAX reads the flag once, then keeps it
    try:
        return lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _scopes_in(lowered_text: str) -> set:
    """The ``SCOPES`` in a lowered program's name-stack locations
    (``attention/dot_general``; not file or function names)."""
    names = set(re.findall(r'loc\("([^"]+)"', lowered_text))
    return {p for name in names if "/" in name and not name.endswith(".py")
            for p in map(_unwrap, name.split("/")) if p in SCOPES}


def _innermost(spans: list, starts: list, t: float) -> str:
    """Name of the innermost span that contains ``t``: the latest to start
    of those still open (``spans`` sorted by start, ``(start, end,
    name)``; ``starts`` their starts)."""
    for s, e, n in reversed(spans[:bisect.bisect_right(starts, t)]):
        if e > t:
            return n
    return "between spans"


def _label(name: str, args: dict) -> str:
    return f"{name}[{args['what']}]" if "what" in args else name


def _stable(module: str) -> str:
    """``jit_stage_bwd(1234)`` -> ``stage_bwd``."""
    name = trace.program(module)
    return name[4:] if name.startswith("jit_") else name


def _depth_steps(dispatched: list, started: list) -> tuple:
    """Change times and the queue depth from each on: +1 at each dispatch,
    -1 at each start."""
    ch = sorted([(t, 1) for t in dispatched] + [(t, -1) for t in started])
    times, depth, d = [], [], 0
    for t, x in ch:
        d += x
        times.append(t)
        depth.append(d)
    return times, depth


def _starved(times: list, depth: list, a: float, b: float) -> float:
    """Length of [a, b) in which the queue depth is 0 or below."""
    i = bisect.bisect_right(times, a)
    d = depth[i - 1] if i else 0
    t, out = a, 0.0
    while t < b:
        nxt = times[i] if i < len(times) and times[i] < b else b
        if d <= 0:
            out += nxt - t
        if nxt < b:
            d = depth[i]
            i += 1
        t = nxt
    return out


def reduce(ev: dict, device_ids, op_scopes: dict | None = None,
           top: int = 10) -> dict:
    """What the program's spans and scopes say about the window.

    * ``busy_s``, ``window_s``: as ``bench.trace.reduce``.
    * ``idle_gaps``: the ``top`` longest gaps between device ops, each
      named by the innermost span the host was in at its middle, the
      program's or the harness's (a sync span with what it reads).
    * ``scope_s``: device self seconds by named scope (``op_scopes``:
      instruction -> scope), summed over devices; ``unscoped`` the rest.
    * ``program_s``: device seconds in each program's ``XLA Modules``
      events, by program name, summed over devices.
    * ``queue``: per device, ``(times_ns, depth)``: the engine programs
      dispatched to it (ends of spans that name a ``program``) less those
      started on it (modules of those programs). Eager array ops are in
      neither count; ``eager_s`` is their device seconds per device.
    * ``starved_s``, per device: idle time in the window with queue depth
      0, when the host had not yet issued the device's next work.
    * ``idle_by_span``: ``[idle_s, starved_s]`` summed over devices, by
      the span that names each gap; gaps under ``SHORT_GAP_NS`` together.
    * ``steps``, ``syncs``: ``pipeline.step`` and ``pipeline.sync`` spans
      that start in the window.
    """
    w0, w1 = trace.window(ev)
    spans = ev.get("spans", [])
    op_scopes = op_scopes or {}
    # by start, the longer first where two start together
    host = sorted([(s, s + d, n) for n, s, d in ev["host"]
                   if n in trace.HOST_SPANS]
                  + [(s, s + d, _label(n, a)) for n, s, d, a in spans],
                  key=lambda h: (h[0], -h[1]))
    host_starts = [s for s, _, _ in host]
    programs = {a["program"] for _, _, _, a in spans if "program" in a}
    dispatched: dict = {}
    for _, s, d, a in spans:
        if "program" in a:
            for dev in str(a.get("devices", "")).split(","):
                if dev.strip():
                    dispatched.setdefault(int(dev), []).append(s + d)

    busy, gaps, scope_s, program_s = {}, [], {}, {}
    queue, starved, eager = {}, {}, {}
    for dev in device_ids:
        modules = ev.get("modules", {}).get(dev, [])
        prog = trace._enclosing(modules)
        ivs = []
        eager[dev] = 0.0
        for name, s, a, b, own in trace._self_times(
                ev["devices"].get(dev, []), w0, w1):
            ivs.append((a, b))
            scope = op_scopes.get(trace.instruction(name), "unscoped")
            scope_s[scope] = scope_s.get(scope, 0.0) + own / 1e9
            p = prog(s)
            if p is not None and _stable(p) not in programs:
                eager[dev] += own / 1e9
        for name, s, d in modules:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                key = trace.program(name)
                program_s[key] = program_s.get(key, 0.0) + (b - a) / 1e9
        merged = trace._union(ivs)
        busy[dev] = sum(b - a for a, b in merged) / 1e9
        started = [s for name, s, _ in modules if _stable(name) in programs]
        times, depth = _depth_steps(dispatched.get(dev, []), started)
        queue[dev] = (times, depth)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2], strict=True)
                if b > a]
        gaps += [(dev, a, b, _starved(times, depth, a, b)) for a, b in idle]
        starved[dev] = sum(g[3] for g in gaps if g[0] == dev) / 1e9

    by_span: dict = {}
    for dev, a, b, st in gaps:
        what = SHORT_GAPS if b - a < SHORT_GAP_NS else \
            _innermost(host, host_starts, (a + b) / 2)
        acc = by_span.setdefault(what, [0.0, 0.0])
        acc[0] += (b - a) / 1e9
        acc[1] += st / 1e9
    gaps = sorted(gaps, key=lambda g: g[1] - g[2])[:top]

    def in_window(name):
        return sum(1 for n, s, _, _ in spans if n == name and w0 <= s < w1)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy,
        "idle_gaps": [(f"{_innermost(host, host_starts, (a + b) / 2)} "
                       f"(device {dev})", (b - a) / 1e9) for dev, a, b, _ in gaps],
        "idle_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1][0])),
        "scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
        "program_s": dict(sorted(program_s.items(), key=lambda kv: -kv[1])),
        "programs": sorted(programs),
        "queue": queue,
        "starved_s": starved if programs else {},
        "eager_s": eager,
        "steps": in_window(STEP_SPAN),
        "syncs": in_window(SYNC_SPAN),
    }


def read_trace(ctx: dict, with_scopes: bool = False) -> dict | None:
    """``reduce`` of the trace the harness is reducing, for a metric
    reader; None where there is no trace to read."""
    path = locate()
    if path is None:
        return None
    ids = tuple(d.id for d in ctx["devices"])
    if not with_scopes:
        return _reduced(path, ids)
    text = step_hlo(ctx["cell"], ctx["devices"])
    if text is None:
        return None
    return reduce(events(path), ids, scopes_of(text))


@functools.lru_cache(maxsize=2)
def _reduced(path: str, device_ids: tuple) -> dict:
    return reduce(events(path), device_ids)
