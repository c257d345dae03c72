"""Batched serving driver: prefill a batch of prompts, then decode tokens
step by step against the KV/SSM cache.

    python -m repro.launch.serve --arch mamba2-130m --smoke \
        --batch 4 --prompt-len 32 --gen 16

With ``--plan-topo`` deployment planning routes through the planner
service; adding ``--observe`` closes the paper's §4.3 loop: measured
decode-step wall times are logged to ``--telemetry-dir`` and fed to
``PlannerService.observe`` — past the drift threshold the cached plan is
invalidated and re-searched under a recalibrated cost model.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.configs.shapes import InputShape
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (
    abstract_params, decode_step, init_cache, init_params, input_specs,
    loss_fn)
from repro.parallel.sharding import axis_rules


def plan_deployment(cfg, topo_name: str, *, cache_dir=None,
                    iterations: int = 20, n_groups: int = 20,
                    batch: int = 4, seq: int = 32, name: str = "",
                    telemetry_dir: str | None = None,
                    drift_threshold: float = 0.25):
    """Route deployment planning through the planner service: repeated
    launches on the same (model, topology) are served from the plan cache
    without re-running MCTS; perturbed topologies warm-start the search.
    Returns (response, service, grouped_graph, topology) so callers can
    feed observed step times back via ``service.observe``."""
    from repro.core import tag as tag_mod
    from repro.service import PlannerService
    from repro.service.cli import TOPOLOGIES
    if topo_name not in TOPOLOGIES:
        raise SystemExit(f"unknown --plan-topo {topo_name!r}; "
                         f"choose from {sorted(TOPOLOGIES)}")
    # input_specs handles frontend archs (prefix inputs, token budget)
    specs = input_specs(cfg, InputShape(f"plan_{batch}x{seq}", seq, batch,
                                        "train"))
    topo = TOPOLOGIES[topo_name]()
    gg = tag_mod.build_grouped(
        lambda p, b: loss_fn(cfg, p, b, remat=False)[0],
        abstract_params(cfg), specs, name, n_groups)
    svc = PlannerService(cache_dir=cache_dir, telemetry_dir=telemetry_dir,
                         drift_threshold=drift_threshold)
    resp = svc.plan_graph(gg, topo, iterations=iterations)
    return resp, svc, gg, topo


def generate(cfg, params, prompts, gen_tokens: int, rules,
             prefix=None, stats: dict | None = None):
    """prompts: (B, P) int32. Returns (B, gen_tokens) int32.

    When ``stats`` is given it is filled with per-phase wall times
    (``prefill_s``, ``decode_s``, ``decode_steps``): the prefill phase
    absorbs the one-off JIT compile, so ``decode_s / decode_steps`` is a
    steady-state per-step time usable as an observed step measurement.
    """
    B, P = prompts.shape
    total = P + gen_tokens + (cfg.frontend_tokens
                              if cfg.frontend != "none" else 0)
    cache = init_cache(cfg, B, total)

    @jax.jit
    def step(params, cache, tok, pos):
        with axis_rules(rules):
            logits, cache = decode_step(cfg, params, cache, tok, pos)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    # prefill by stepping the prompt (cache-building path is the decode
    # path; a fused prefill exists as launch.steps.make_prefill_step)
    t0 = time.time()
    tok = prompts[:, :1]
    pos = 0
    for i in range(P):
        nxt, cache = step(params, cache, prompts[:, i:i + 1],
                          jnp.asarray(pos, jnp.int32))
        pos += 1
    jax.block_until_ready(nxt)
    t_prefill = time.time() - t0

    t0 = time.time()
    out = []
    cur = nxt
    for _ in range(gen_tokens):
        out.append(cur)
        cur, cache = step(params, cache, cur, jnp.asarray(pos, jnp.int32))
        pos += 1
    res = jnp.concatenate(out, axis=1)
    jax.block_until_ready(res)
    if stats is not None:
        stats.update(prefill_s=t_prefill, decode_s=time.time() - t0,
                     decode_steps=gen_tokens)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-topo", default=None,
                    help="plan deployment on this topology via the planner "
                         "service before serving (testbed/cloud/tpu/...)")
    ap.add_argument("--plan-cache", default=".plans",
                    help="plan-store directory for --plan-topo")
    ap.add_argument("--plan-iters", type=int, default=20)
    ap.add_argument("--observe", action="store_true",
                    help="with --plan-topo: log measured step times and "
                         "feed them back through PlannerService.observe "
                         "(drift -> recalibrate -> replan)")
    ap.add_argument("--telemetry-dir", default=".telemetry",
                    help="measurement log for --observe")
    ap.add_argument("--drift-threshold", type=float, default=0.25)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.smoke else get_config(args.arch)
    plan = None
    if args.plan_topo:
        resp, svc, gg, topo = plan_deployment(
            cfg, args.plan_topo, cache_dir=args.plan_cache,
            iterations=args.plan_iters, batch=args.batch,
            seq=args.prompt_len, name=args.arch,
            telemetry_dir=args.telemetry_dir if args.observe else None,
            drift_threshold=args.drift_threshold)
        plan = (resp, svc, gg, topo)
        print(f"plan[{args.plan_topo}] source={resp.source} "
              f"iters={resp.iterations_run} "
              f"time={resp.time:.4f}s speedup={resp.speedup:.3f} "
              f"stats={svc.stats()}")
    mesh = mesh_mod.make_host_mesh()
    rules = steps_mod.baseline_rules(mesh)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    t0 = time.time()
    stats: dict = {}
    out = generate(cfg, params, prompts, args.gen, rules, stats=stats)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.1f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s; "
          f"prefill {stats['prefill_s']:.1f}s incl. compile, "
          f"decode {stats['decode_s']:.1f}s)")
    print("sample:", np.asarray(out[0])[:16])

    if args.observe and plan is not None:
        # paper §4.3: feed the measured steady-state per-step wall time
        # (decode phase only — prefill absorbs the one-off JIT compile)
        # back into the planner: telemetry always, invalidation + warm
        # replanning under a recalibrated cost model past the threshold.
        # On CPU hosts this observed time is far from the simulated
        # cluster step, so expect an immediate drift -> replan.
        resp, svc, gg, topo = plan
        step_time = stats["decode_s"] / max(stats["decode_steps"], 1)
        fb = svc.observe(gg, topo, step_time, iterations=args.plan_iters)
        msg = f"observe[{args.plan_topo}] step={step_time:.4f}s kind={fb.kind}"
        if fb.report is not None:
            msg += f" drift={fb.report.drift:.3f}"
        if fb.kind == "replanned":
            msg += (f" stale={fb.stale_time:.4f}s "
                    f"new={fb.response.time:.4f}s improved={fb.improved}")
        print(msg, flush=True)
    return out


if __name__ == "__main__":
    main()
