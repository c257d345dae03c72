"""Spans on two sinks: the in-memory tracer and the profiler's trace, and
the pipeline engine's spans on the device trace's clock."""
import os
import subprocess
import sys
import textwrap

import jax
from jax.profiler import TraceAnnotation
import pytest

from repro.obs import spans as spans_mod
from repro.obs.spans import Tracer

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _profile(tmp_path, body):
    """Run ``body`` under a profiler session; the host spans it left, as
    ``(name, args)``."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        body()
    path = next(iter(sorted(tmp_path.rglob("*.xplane.pb"))))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith("demo.")]
    return out


@pytest.mark.parametrize("tracer_on,profiler_on", [
    (False, False), (True, False), (False, True), (True, True)])
def test_span_feeds_each_sink_that_is_on(tmp_path, tracer_on, profiler_on):
    tr = Tracer(enabled=tracer_on)

    def body():
        with tr.span("demo.outer", cat="pipeline", stage=1):
            with tr.span("demo.inner", cat="pipeline", what="loss"):
                pass

    if profiler_on:
        seen = _profile(tmp_path, body)
        assert sorted(seen) == [("demo.inner", {"what": "loss"}),
                                ("demo.outer", {"stage": 1})]
    else:
        assert not TraceAnnotation.is_enabled()
        body()
    names = [(s.name, s.depth, s.args) for s in tr.spans()]
    if tracer_on:
        assert sorted(names) == [("demo.inner", 1, {"what": "loss"}),
                                 ("demo.outer", 0, {"stage": 1})]
    else:
        assert names == []


def test_span_is_a_shared_no_op_with_both_sinks_off():
    tr = Tracer(enabled=False)
    assert not TraceAnnotation.is_enabled()
    assert tr.span("demo.x", a=1) is spans_mod._NULL_SPAN
    assert spans_mod.span("demo.y") is spans_mod._NULL_SPAN


ENGINE = """
    import collections, glob, json, sys, tempfile
    sys.path[:0] = [{root!r}, {src!r}]
    import jax, jax.numpy as jnp
    from jax._src.array import ArrayImpl
    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.exec import PipelineRunner, split_model
    from repro.exec.stages import StagePlan, StageSpec
    from repro.launch import steps as steps_mod
    from repro.optim.adam import AdamW
    from bench import spans as bspans

    S, M = 2, 4
    cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
    assert cfg.tie_embeddings
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {{"tokens": jnp.ones((8, 16), jnp.int32),
              "labels": jnp.ones((8, 16), jnp.int32)}}
    plan = StagePlan(
        stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0, grad_bytes=0,
                          out_bytes=1e5, sync="allreduce", n_devices=1,
                          gpu_type="cpu") for i in range(S)],
        placement=(0, 1), n_micro=M)
    devs = jax.devices()
    sp, fns, keys, tied = split_model(cfg, params, S)
    runner = PipelineRunner(fns, plan, [[devs[0]], [devs[1]]],
                            schedule="1f1b", n_micro=M, mb_keys=keys,
                            tied_ref=tied)
    opt = AdamW()
    p = runner.place_params(sp)
    s = [opt.init(x) for x in p]
    fn = steps_mod.make_pipeline_train_step(opt, runner)
    p, s, _ = fn(p, s, jnp.asarray(0, jnp.int32), batch)    # compiles

    counts = collections.Counter()

    def counted(name, real):
        def f(*a, **k):
            counts[name] += 1
            return real(*a, **k)
        return f
    real_bur = jax.block_until_ready
    jax.block_until_ready = counted("block_until_ready", real_bur)
    for meth in ("__float__", "__int__", "__bool__", "__array__", "item",
                 "tolist"):
        setattr(ArrayImpl, meth, counted("read", getattr(ArrayImpl, meth)))
    jax.device_get = counted("read", jax.device_get)

    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        p, s, _ = fn(p, s, jnp.asarray(1, jnp.int32), batch)
    jax.block_until_ready = real_bur
    traced_counts = dict(counts)
    ev = bspans.events(glob.glob(d + "/**/*.xplane.pb", recursive=True)[0])

    # the same step into the in-memory tracer (what --trace-dir writes)
    from repro.obs.spans import Tracer, set_tracer
    tracer = Tracer(enabled=True)
    set_tracer(tracer)
    p, s, _ = fn(p, s, jnp.asarray(2, jnp.int32), batch)
    recorded = collections.Counter(sp.name for sp in tracer.spans())
    print(json.dumps({{"counts": traced_counts, "spans": ev["spans"],
                      "recorded": recorded}}))
"""


@pytest.fixture(scope="module")
def engine_step():
    """One traced ``make_pipeline_train_step`` step of a 2-stage eager
    1F1B pipeline with a tied head, on 2 CPU devices."""
    import json
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = textwrap.dedent(ENGINE.format(
        root=os.path.abspath(ROOT), src=os.path.abspath(os.path.join(ROOT, "src"))))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    got["spans"] = [(n, s, s + d, a) for n, s, d, a in got["spans"]]
    return got


S, M, METRIC_KEYS = 2, 4, 2          # the traced step's stages, microbatches


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


@pytest.mark.parametrize("name,count", [
    ("pipeline.step", 1), ("pipeline.F", S * M), ("pipeline.B", S * M),
    ("pipeline.tied_head", 1), ("pipeline.tied_grad", 1),
    ("pipeline.grad_accum", S * (M - 1)), ("step.grad_sqnorm", S),
    ("step.optimizer", S), ("pipeline.sync", 1 + METRIC_KEYS * M + S),
    ("pipeline.transfer", S * M + (S - 1) * M * 2)])
def test_engine_writes_each_named_span(engine_step, name, count):
    # transfers: a microbatch per stage, and per boundary a carry forward
    # and a gradient back
    assert len(_named(engine_step["spans"], name)) == count


def test_engine_spans_carry_stage_microbatch_and_program(engine_step):
    spans = engine_step["spans"]
    for kind, program in (("F", "stage_fwd"), ("B", "stage_bwd")):
        evs = _named(spans, f"pipeline.{kind}")
        assert {(a["stage"], a["mb"]) for *_, a in evs} == {
            (s, m) for s in range(S) for m in range(M)}
        assert {a["program"] for *_, a in evs} == {program}
        assert all(str(a["devices"]) == str(a["stage"]) for *_, a in evs)
    assert {a["program"] for *_, a in _named(spans, "step.optimizer")} == {
        "adamw_update"}
    assert sorted(a["what"] for *_, a in _named(spans, "pipeline.sync")) == (
        ["grad_norm"] * S + ["loss"] + ["metric"] * METRIC_KEYS * M)


def test_engine_spans_nest(engine_step):
    spans = engine_step["spans"]

    def inside(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)
    step = _named(spans, "pipeline.step")
    events = _named(spans, "pipeline.F") + _named(spans, "pipeline.B")
    for sp in _named(spans, "pipeline.transfer"):
        assert inside(sp, events), sp
    for name in ("pipeline.F", "pipeline.B", "pipeline.grad_accum",
                 "pipeline.tied_head", "pipeline.tied_grad"):
        assert all(inside(sp, step) for sp in _named(spans, name)), name
    syncs = _named(spans, "pipeline.sync")
    assert sum(inside(sp, step) for sp in syncs) == 1 + METRIC_KEYS * M
    for sp in _named(spans, "step.optimizer") + _named(spans, "step.grad_sqnorm"):
        assert not inside(sp, step)


def test_no_record_path_blocks_only_where_it_reads(engine_step):
    # record=False adds no block_until_ready; every host read is the one
    # the step always made (loss, each metric of each microbatch, each
    # stage's squared gradient norm), each under its own sync span
    counts = engine_step["counts"]
    assert counts.get("block_until_ready", 0) == 0
    assert counts["read"] == 1 + METRIC_KEYS * M + S
    assert counts["read"] == len(_named(engine_step["spans"], "pipeline.sync"))


def test_in_memory_tracer_gets_the_same_engine_spans(engine_step):
    import collections
    traced = collections.Counter(sp[0] for sp in engine_step["spans"])
    assert engine_step["recorded"] == dict(traced)
