"""XLA profiler hook: per-collective attribution for instrumented steps.

Wraps a step callable in ``jax.profiler.trace(..., create_perfetto_trace
=True)``, then parses the emitted perfetto/Chrome trace into the
per-collective sample shape ``runtime.telemetry.StepRecord.collectives``
carries ({kind, nbytes, n_dev, nominal_bw, link, time, pair?}) — the
input of ``runtime.calibration.fit_profile``'s per-link-pair tier. This
closes the ROADMAP telemetry item: real hardware feeds the calibration
the same samples the replay executors synthesize.

Off a TPU, a missing ``jax.profiler`` or a trace with no parseable file
(CPU backends sometimes emit host tracks only) degrades to
``samples == []`` with a ``meta`` dict saying why. A trace that fails,
and on a TPU any missing profile, raises: the step is never rerun.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

# XLA op-name fragments -> StepRecord collective kinds
_COLLECTIVE_PATTERNS = (
    (re.compile(r"all[-_]?reduce", re.I), "allreduce"),
    (re.compile(r"reduce[-_]?scatter", re.I), "allreduce"),
    (re.compile(r"all[-_]?gather", re.I), "allreduce"),
    (re.compile(r"all[-_]?to[-_]?all", re.I), "xfer"),
    (re.compile(r"collective[-_]?permute", re.I), "xfer"),
    (re.compile(r"\b(send|recv)\b|copy[-_]?start|copy[-_]?done", re.I),
     "xfer"),
)
# arg keys the profiler may use for moved bytes, in preference order
_BYTES_KEYS = ("nbytes", "bytes", "bytes_accessed", "bytes accessed",
               "size", "shape_size")


def profiler_available() -> bool:
    try:
        import jax.profiler  # noqa: F401
        return True
    except Exception:
        return False


def classify_op(name: str) -> str | None:
    """Collective kind of an XLA/TSL op name, or None for non-collectives."""
    for pat, kind in _COLLECTIVE_PATTERNS:
        if pat.search(name):
            return kind
    return None


def _event_bytes(args: dict) -> float:
    for k in _BYTES_KEYS:
        v = args.get(k)
        if v is None:
            continue
        try:
            return float(v)
        except (TypeError, ValueError):
            continue
    return 0.0


def find_trace_files(log_dir: str) -> list:
    """Perfetto/Chrome trace JSONs under a profiler log dir (newest run
    first)."""
    pats = ("**/*.trace.json.gz", "**/*.trace.json",
            "**/perfetto_trace.json.gz", "**/perfetto_trace.json")
    out: list = []
    for pat in pats:
        out.extend(glob.glob(os.path.join(log_dir, pat), recursive=True))
    return sorted(set(out), key=lambda p: os.path.getmtime(p),
                  reverse=True)


def parse_trace_collectives(path: str, *, nominal_bw: float = 0.0,
                            n_dev: int = 2, link: str = "intra",
                            pair: str | None = None) -> list:
    """Collective samples from one trace-event JSON(.gz) file.

    Complete (``ph == "X"``) events whose name matches a collective
    pattern become samples; ``dur`` is microseconds per the trace-event
    contract. ``nominal_bw``/``n_dev``/``link``/``pair`` supply the
    cluster-side context the device trace cannot know.
    """
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    samples = []
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        name = str(e.get("name", ""))
        kind = classify_op(name)
        if kind is None:
            continue
        dur_us = float(e.get("dur", 0.0))
        if dur_us <= 0:
            continue
        sample = {"kind": kind, "nbytes": _event_bytes(e.get("args", {})),
                  "n_dev": n_dev, "nominal_bw": nominal_bw, "link": link,
                  "time": dur_us / 1e6, "op": name}
        if pair:
            sample["pair"] = pair
        samples.append(sample)
    return samples


def profile_step(fn, *args, log_dir: str, nominal_bw: float = 0.0,
                 n_dev: int = 2, link: str = "intra",
                 pair: str | None = None, **kwargs) -> tuple:
    """Run ``fn(*args, **kwargs)`` once under an XLA profiler trace and
    parse per-collective samples out of the result.

    Returns ``(out, samples, meta)`` with ``meta["profiler"] == "ok"``. A
    trace that fails re-raises: ``fn`` is never run a second time (its
    arguments may be donated). Off a TPU, a missing profiler or trace
    file degrades to ``samples == []`` with ``meta["profiler"]`` set to
    ``"unavailable"`` / ``"no_trace"``; on a TPU both raise, since a
    chip run whose profile silently vanished would mislead.
    """
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if not profiler_available():
        if on_tpu:
            raise RuntimeError("jax.profiler is unavailable on a TPU backend")
        return fn(*args, **kwargs), [], {"profiler": "unavailable"}
    import jax.profiler
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    samples: list = []
    parsed_from = None
    for path in find_trace_files(log_dir):
        try:
            samples = parse_trace_collectives(
                path, nominal_bw=nominal_bw, n_dev=n_dev, link=link,
                pair=pair)
            parsed_from = path
            break
        except (OSError, ValueError, KeyError):
            continue
    if parsed_from is None:
        if on_tpu:
            raise RuntimeError(f"profiler wrote no parsable trace under "
                               f"{log_dir}")
        return out, [], {"profiler": "no_trace", "log_dir": log_dir}
    return out, samples, {"profiler": "ok", "trace_file": parsed_from,
                          "n_collectives": len(samples)}


def attach_collectives(record, samples: list, meta: dict | None = None):
    """Merge profiler-derived samples into a ``StepRecord`` in place (and
    stamp how they were obtained); returns the record."""
    record.collectives = list(record.collectives) + list(samples)
    record.meta = dict(record.meta, xla_profiler=(meta or {}))
    return record
