"""End-to-end training driver.

    python -m repro.launch.train --arch yi-6b --smoke \
        --steps 20 --batch 8 --seq 128

Runs the full loop on whatever devices exist (CPU smoke by default):
synthetic data pipeline -> jitted train step (sharded when a mesh is
requested) -> checkpointing -> metrics log. ``--tag-search`` runs the TAG
strategy search on a reduced trace of the model first and applies the
resulting execution plan's axis rules.
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass, field
import json
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import SyntheticDataset
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim.adam import AdamW


def resolve_pipeline(plan, mode: str):
    """Decide whether a lowered TAG plan's PIPE stages can really run.

    Returns the ``StagePlan`` to execute, or ``None`` for the single-mesh
    path — emitting an explicit log line either way, so a strategy with
    PIPE actions is never *silently* degraded to pure-DP axis rules.
    """
    sp = plan.stage_plan
    if sp is None:
        if plan.summary.get("options", {}).get("PIPE"):
            print("TAG pipeline: strategy has PIPE actions but no "
                  "multi-group pipeline spine; using single-mesh axis "
                  "rules", flush=True)
        return None
    if mode == "off":
        print(f"TAG pipeline: --pipeline off; degrading "
              f"{sp.n_stages}-stage plan to single-mesh axis rules",
              flush=True)
        return None
    from repro.exec.stages import PipelineInfeasible
    try:
        mesh_mod.stage_device_sets(sp)
    except PipelineInfeasible as e:
        print(f"WARNING: TAG pipeline fallback — {e}; degrading to "
              f"single-mesh DP axis rules", flush=True)
        return None
    sched = sp.schedule if mode == "auto" else mode
    print(f"TAG pipeline: executing {sp.n_stages} stages "
          f"(schedule={sched}, placement={list(sp.placement)}, "
          f"sync={[s.sync for s in sp.stages]})", flush=True)
    return sp


@dataclass
class TrainRun:
    """What a training run leaves behind: per-step loss, global grad norm
    (before clipping) and wall seconds from dispatch until the step's
    outputs are ready (host batch generation excluded; the first step
    includes compilation), plus the final params and optimizer state."""
    losses: list = field(default_factory=list)
    grad_norms: list = field(default_factory=list)
    step_s: list = field(default_factory=list)
    params: object = None
    opt_state: object = None

    def record(self, seconds, loss, grad_norm):
        self.step_s.append(seconds)
        self.losses.append(float(loss))
        self.grad_norms.append(float(grad_norm))


def _stage_key(s: int) -> str:
    return f"stage{s}"


def _export_spans(args):
    """Write the session's planner/search spans as a Chrome trace
    (``--trace-dir``); no-op when tracing is off or nothing was
    recorded."""
    if not getattr(args, "trace_dir", ""):
        return
    from repro.obs import chrome_trace, write_chrome_trace
    from repro.obs.spans import get_tracer
    tracer = get_tracer()
    if not tracer.spans():
        return
    path = write_chrome_trace(
        os.path.join(args.trace_dir, "trace_spans.json"),
        chrome_trace(tracer.to_chrome(process_name="train"),
                     arch=args.arch, kind="spans"))
    print(f"trace: wrote {path} ({len(tracer.spans())} spans)",
          flush=True)


def _run_id(args) -> str:
    """One run id for the whole job: groups the spool shard under
    /traces/<run_id> AND the telemetry records under /runs/<run_id> on
    the health analyzer side."""
    return getattr(args, "run_id", "") or f"train-{args.arch}"


def _make_spool(args):
    """``--spool-dir``: a ``SpoolWriter`` shard for this training
    process, feeding the cross-process trace collector (``repro-plan
    serve-metrics --spool-dir`` on the other end). None when unset —
    tests drive these entry points with hand-built Namespaces."""
    spool_dir = getattr(args, "spool_dir", "")
    if not spool_dir:
        return None
    from repro.obs.collector import SpoolWriter
    return SpoolWriter(spool_dir, run_id=_run_id(args), name="train",
                       meta={"arch": args.arch})


def _drain_tracer_to_spool(spool):
    """Ship this process's recorded planner/search spans (if any) into
    its spool shard alongside the step/stage events."""
    if spool is None:
        return
    from repro.obs.spans import get_tracer
    tracer = get_tracer()
    if tracer.spans():
        spool.emit_tracer(tracer)


def run_pipeline(args, cfg, stage_plan):
    """Train via a pipeline execution engine (repro.exec): the eager
    per-event engine, or the scan-rolled compiled engine
    (``--engine scan``)."""
    from repro.exec import (
        CompiledPipelineRunner, PipelineRunner, split_model)
    from repro.optim.adam import AdamW

    # tests drive run_pipeline with hand-built Namespaces — default the
    # newer knobs instead of requiring them
    engine = getattr(args, "engine", "eager")
    if engine not in ("eager", "scan"):
        raise ValueError(f"unknown engine {engine!r} (eager|scan)")
    schedule = stage_plan.schedule if args.pipeline == "auto" \
        else args.pipeline
    n_chunks = max(2, args.n_chunks) if schedule == "interleaved" else 1
    n_micro = max(1, args.n_micro)
    while n_micro > 1 and (args.batch % n_micro
                           or (schedule == "interleaved"
                               and n_micro % stage_plan.n_stages)):
        n_micro -= 1
    if schedule == "interleaved" and n_micro % stage_plan.n_stages:
        raise ValueError(
            f"interleaved needs n_micro divisible by "
            f"{stage_plan.n_stages} stages (and by batch {args.batch}); "
            f"none <= {args.n_micro} works — pick --n-micro/--batch "
            f"accordingly or another --pipeline schedule")
    if n_micro != args.n_micro:
        print(f"pipeline: n_micro {args.n_micro} -> {n_micro} "
              f"(must divide batch {args.batch}"
              + (f" and be a multiple of {stage_plan.n_stages} stages"
                 if schedule == "interleaved" else "") + ")", flush=True)

    device_sets = mesh_mod.stage_device_sets(stage_plan)

    # static preflight before any parameter gets allocated: the resolved
    # (schedule, n_micro, n_chunks) triple and the actual device sets,
    # verified device-free — errors abort here, warnings print
    from repro.exec.schedule import make_schedule
    from repro.verify import PlanVerificationError, verify_preflight
    pre = verify_preflight(
        stage_plan,
        make_schedule(schedule, stage_plan.n_stages, n_micro,
                      n_chunks=n_chunks),
        n_micro, n_chunks=n_chunks,
        device_counts=[len(d) for d in device_sets])
    if pre.errors():
        raise PlanVerificationError(
            pre, context=f"launch preflight ({schedule}, "
                         f"S={stage_plan.n_stages}, n_micro={n_micro})")
    for d in pre.warnings():
        print(f"preflight: {d.format()}", flush=True)

    splits = stage_plan.layer_splits(cfg.num_periods, n_chunks=n_chunks)
    stage_params, fns, mb_keys, tied = split_model(
        cfg, init_params(cfg, jax.random.PRNGKey(args.seed)),
        stage_plan.n_stages * n_chunks, splits=splits)

    store = None
    if args.telemetry_dir:
        from repro.runtime.telemetry import MeasurementStore
        store = MeasurementStore(args.telemetry_dir)
    spool = _make_spool(args)
    runner_kw = dict(
        schedule=schedule, n_micro=n_micro, n_chunks=n_chunks,
        mb_keys=mb_keys, tied_ref=tied, store=store, spool=spool,
        meta={"arch": args.arch, "batch": args.batch, "seq": args.seq,
              "launcher": "train", "engine": engine,
              "run_id": _run_id(args)})
    if engine == "scan":
        runner = CompiledPipelineRunner(
            fns, stage_plan, device_sets,
            unroll=max(1, getattr(args, "scan_unroll", 1)), **runner_kw)
        print(f"pipeline engine: scan (rolled lax.scan programs, "
              f"unroll={runner.unroll})", flush=True)
    else:
        runner = PipelineRunner(fns, stage_plan, device_sets, **runner_kw)

    opt = AdamW(lr=args.lr)
    # the whole model was built on the default device: once each stage's
    # slice is committed to its own devices, drop the default-device copy
    params_list = runner.place_params(stage_params)
    del stage_params
    n_virtual = len(params_list)
    opt_state_list = [opt.init(p) for p in params_list]  # on p's devices
    start_step = 0
    if getattr(args, "resume", False) and args.ckpt_dir \
            and latest_step(args.ckpt_dir) is not None:
        start_step, tree = load_checkpoint(args.ckpt_dir)
        keys = [_stage_key(u) for u in range(n_virtual)]
        if sorted(tree["params"]) != sorted(keys):
            raise ValueError(
                f"checkpoint in {args.ckpt_dir} is not a "
                f"{n_virtual}-stage pipeline checkpoint — "
                f"resume it with the matching stage map and schedule "
                f"(or without --tag-search for single-mesh checkpoints)")
        params_list = [runner.place(runner.phys(u), tree["params"][k])
                       for u, k in enumerate(keys)]
        opt_state_list = [runner.place(runner.phys(u),
                                       tree["opt_state"][k])
                          for u, k in enumerate(keys)]
        print(f"resumed pipelined run from step {start_step}", flush=True)
    step_fn = steps_mod.make_pipeline_train_step(opt, runner)

    ds = SyntheticDataset(
        cfg.vocab_size, args.seq, args.batch, seed=args.seed,
        frontend_tokens=cfg.frontend_tokens if cfg.frontend != "none" else 0,
        d_model=cfg.d_model)

    # tests drive run_pipeline with hand-built Namespaces: default, don't
    # assume the full CLI surface
    trace_dir = getattr(args, "trace_dir", None)
    record_steps = store is not None or bool(trace_dir)
    run = TrainRun()
    losses = run.losses
    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = jax.tree.map(jnp.asarray, ds.batch(step))
        t_dispatch = time.perf_counter()
        params_list, opt_state_list, metrics = step_fn(
            params_list, opt_state_list, jnp.asarray(step, jnp.int32),
            batch, record=record_steps)
        jax.block_until_ready((params_list, opt_state_list))
        run.record(time.perf_counter() - t_dispatch, metrics["loss"],
                   metrics["grad_norm"])
        if step % args.log_every == 0:
            chunks = f"x{n_chunks}v" if n_chunks > 1 else ""
            print(f"step {step:5d} loss={metrics['loss']:.4f} "
                  f"ce={metrics['ce']:.4f} "
                  f"gnorm={metrics['grad_norm']:.3f} "
                  f"step_ms={run.step_s[-1] * 1e3:.1f} "
                  f"[pipeline {schedule} x{stage_plan.n_stages}{chunks}]",
                  flush=True)
        if args.ckpt_dir and args.ckpt_every and \
                (step + 1) % args.ckpt_every == 0:
            # per-stage trees keyed by stage (the flat-npz checkpointer
            # walks dicts, not lists)
            save_checkpoint(
                args.ckpt_dir, step + 1,
                {"params": {_stage_key(s): p
                            for s, p in enumerate(params_list)},
                 "opt_state": {_stage_key(s): o
                               for s, o in enumerate(opt_state_list)}})
    dt = time.time() - t_start
    n = max(args.steps - start_step, 1)
    tail = f"; loss {losses[0]:.4f} -> {losses[-1]:.4f}" if losses else ""
    print(f"done: {n} pipelined steps in {dt:.1f}s "
          f"({dt/n*1e3:.0f} ms/step, schedule={schedule}, "
          f"stages={stage_plan.n_stages}, n_micro={n_micro})"
          f"{tail}", flush=True)
    if trace_dir and runner.last_stats is not None:
        from repro.obs import (
            chrome_trace, executed_trace_events, write_chrome_trace)
        events = executed_trace_events(
            runner.last_stats, pid=0,
            process_name=f"executed [{schedule}]",
            n_stages=stage_plan.n_stages)
        path = write_chrome_trace(
            os.path.join(trace_dir, "trace_executed.json"),
            chrome_trace(events, arch=args.arch, schedule=schedule,
                         n_micro=n_micro,
                         n_stages=stage_plan.n_stages))
        print(f"trace: wrote {path} "
              f"({len(runner.last_stats.events)} events)", flush=True)
    _drain_tracer_to_spool(spool)
    run.params, run.opt_state = params_list, opt_state_list
    return run


def run_single(args, cfg, devices=None):
    """Train on one data-parallel mesh over ``devices`` (default: every
    local device) with the jitted single-program step.

    Returns the ``TrainRun``."""
    mesh = mesh_mod.make_host_mesh(devices)
    rules = steps_mod.baseline_rules(mesh)
    opt = AdamW(lr=args.lr)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt_state = opt.init(params)
    start_step = 0
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start_step, tree = load_checkpoint(args.ckpt_dir)
        if _stage_key(0) in tree.get("params", {}):
            raise ValueError(
                f"checkpoint in {args.ckpt_dir} is a per-stage pipeline "
                f"checkpoint — resume it through the pipeline path "
                f"(--tag-search with the same stage map)")
        params, opt_state = tree["params"], tree["opt_state"]
        print(f"resumed from step {start_step}", flush=True)
    # commit the state to the mesh as the step returns it, so step 1
    # hits step 0's compiled program instead of compiling a second one
    params, opt_state = jax.device_put(
        (params, opt_state), NamedSharding(mesh, P()))

    ds = SyntheticDataset(
        cfg.vocab_size, args.seq, args.batch, seed=args.seed,
        frontend_tokens=cfg.frontend_tokens if cfg.frontend != "none" else 0,
        d_model=cfg.d_model)

    options = steps_mod.StepOptions(loss_chunk=args.loss_chunk)
    # params and optimizer state are rebound every step: donating them
    # lets XLA update both in place instead of holding two copies
    step_fn = jax.jit(steps_mod.make_train_step(cfg, opt, rules, options),
                      donate_argnums=(0, 1))

    raw_step_fn = step_fn
    timer = None
    if args.telemetry_dir:
        from repro.runtime.telemetry import MeasurementStore, StepTimer
        timer = StepTimer(MeasurementStore(args.telemetry_dir),
                          meta={"arch": args.arch, "batch": args.batch,
                                "seq": args.seq, "launcher": "train",
                                "run_id": _run_id(args)})
        step_fn = steps_mod.instrument_step(step_fn, timer)

    # profile one post-warmup step (the first is compile-dominated)
    profile_at = -1
    if args.xla_profile:
        profile_at = min(start_step + 1, args.steps - 1)

    spool = _make_spool(args)
    run = TrainRun()
    losses = run.losses
    t_start = time.time()
    for step in range(start_step, args.steps):
        t_step = time.perf_counter()
        batch = jax.tree.map(jnp.asarray, ds.batch(step))
        t_dispatch = time.perf_counter()
        if step == profile_at:
            from repro.obs.xla_profiler import profile_step
            log_dir = os.path.join(
                args.trace_dir or args.telemetry_dir or ".",
                "xla_profile")
            t0 = time.perf_counter()
            out, samples, pmeta = profile_step(
                raw_step_fn, params, opt_state,
                jnp.asarray(step, jnp.int32), batch, log_dir=log_dir)
            wall = time.perf_counter() - t0
            params, opt_state, metrics = out
            print(f"xla-profile: {json.dumps(pmeta)} "
                  f"({len(samples)} collective samples)", flush=True)
            if timer is not None:
                timer.record(wall, collectives=samples)
        else:
            params, opt_state, metrics = step_fn(
                params, opt_state, jnp.asarray(step, jnp.int32), batch)
        jax.block_until_ready((params, opt_state, metrics))
        run.record(time.perf_counter() - t_dispatch, metrics["loss"],
                   metrics["grad_norm"])
        loss = losses[-1]
        if spool is not None:
            spool.emit_span(f"step {step}", t_step, time.perf_counter(),
                            tid=0, cat="train",
                            args={"step": step, "loss": loss})
        if step % args.log_every == 0:
            print(f"step {step:5d} loss={loss:.4f} "
                  f"ce={float(metrics['ce']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"step_ms={run.step_s[-1] * 1e3:.1f}", flush=True)
        if args.ckpt_dir and args.ckpt_every and \
                (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1,
                            {"params": params, "opt_state": opt_state})
    dt = time.time() - t_start
    n = max(args.steps - start_step, 1)
    print(f"done: {n} steps in {dt:.1f}s ({dt/n*1e3:.0f} ms/step); "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)
    if timer is not None:
        print(f"telemetry[{args.telemetry_dir}]: "
              f"{json.dumps(timer.summary())}", flush=True)
    _drain_tracer_to_spool(spool)
    run.params, run.opt_state = params, opt_state
    return run


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (``run_single``/``run_pipeline`` read
    the namespace it parses)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--tag-search", action="store_true",
                    help="run TAG strategy search and apply its plan")
    ap.add_argument("--pipeline",
                    choices=["auto", "off", "gpipe", "1f1b",
                             "interleaved", "zb"],
                    default="auto",
                    help="how to execute PIPE actions in a TAG plan: "
                         "a schedule name runs the pipeline engine, "
                         "auto uses the schedule the searched strategy "
                         "voted for (legacy plans: 1f1b), off forces "
                         "single-mesh rules")
    ap.add_argument("--engine", choices=["eager", "scan"],
                    default="eager",
                    help="pipeline execution engine: eager dispatches "
                         "every schedule event from Python; scan runs "
                         "the compiled scan-rolled engine (per-stage "
                         "lax.scan programs, bulk double-buffered "
                         "boundary transfers, GPipe-like stash)")
    ap.add_argument("--scan-unroll", type=int, default=1,
                    help="lax.scan unroll factor for --engine scan "
                         "(1 keeps compile time flat in n_micro)")
    ap.add_argument("--n-micro", type=int, default=4,
                    help="microbatches per pipelined step")
    ap.add_argument("--n-chunks", type=int, default=2,
                    help="virtual model chunks per stage for the "
                         "interleaved schedule")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--telemetry-dir", default="",
                    help="record per-step telemetry (runtime feedback "
                         "subsystem) to this measurement log")
    ap.add_argument("--trace-dir", default="",
                    help="export Chrome traces here: the executed "
                         "pipeline timeline of the last step plus the "
                         "planner/search span timeline")
    ap.add_argument("--spool-dir", default="",
                    help="append this process's step/stage events and "
                         "spans to a shard in this live-observability "
                         "spool directory (merged across processes by "
                         "the trace collector / served by repro-plan "
                         "serve-metrics)")
    ap.add_argument("--run-id", default="",
                    help="run id grouping this job's spool shard with "
                         "other processes' shards in /traces/<run_id> "
                         "and its telemetry under /runs/<run_id> "
                         "(default: train-<arch>)")
    ap.add_argument("--xla-profile", action="store_true",
                    help="wrap one post-warmup step in a jax.profiler "
                         "trace and record per-collective samples into "
                         "the telemetry log (no-op if the profiler "
                         "backend is unavailable)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.trace_dir:
        from repro.obs.spans import Tracer, set_tracer
        set_tracer(Tracer(enabled=True))

    cfg = get_reduced(args.arch) if args.smoke else get_config(args.arch)

    if args.tag_search:
        from repro.core import tag as tag_mod
        from repro.core.plan import lower_strategy
        from repro.core.device import tpu_pods
        from repro.models import loss_fn as model_loss
        red = get_reduced(args.arch)
        rp = init_params(red, jax.random.PRNGKey(0))
        ds0 = SyntheticDataset(red.vocab_size, 32, 4,
                               frontend_tokens=red.frontend_tokens
                               if red.frontend != "none" else 0,
                               d_model=red.d_model)
        rb = jax.tree.map(jnp.asarray, ds0.batch(0))
        topo = tpu_pods()
        result = tag_mod.optimize(
            lambda p, b: model_loss(red, p, b, remat=False)[0],
            rp, rb, topo, name=args.arch, iterations=24, n_groups=24)
        plan = lower_strategy(result.strategy, result.gg, topo,
                              mesh_mod.make_host_mesh(),
                              n_micro=args.n_micro)
        print(f"TAG plan: speedup={result.speedup:.2f}x "
              f"summary={json.dumps(plan.summary)}", flush=True)
        stage_plan = resolve_pipeline(plan, args.pipeline)
        if stage_plan is not None:
            losses = run_pipeline(args, cfg, stage_plan).losses
            _export_spans(args)
            return losses

    losses = run_single(args, cfg).losses
    _export_spans(args)
    return losses


if __name__ == "__main__":
    main()
