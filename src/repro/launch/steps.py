"""Builders for the jitted train / prefill / serve steps, plus the logical
axis-rule sets that TAG strategies lower into.

The returned step functions enter the ``axis_rules`` context *inside* the
jitted body, so model-level ``logical_shard`` constraints are applied at
trace time under whatever mesh the launcher chose.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import model as model_mod
from repro.optim.adam import AdamW, clip_by_global_norm
from repro.parallel.sharding import AxisRules, axis_rules, logical_spec


def baseline_rules(mesh, *, overrides: dict | None = None,
                   grad_sync: dict | None = None) -> AxisRules:
    """Paper-faithful DP(+TP) baseline: batch over pod+data, tensor dims over
    model. TAG strategies produce ``overrides``/``grad_sync`` on top."""
    multi = "pod" in mesh.axis_names
    rules = {
        "batch": ("pod", "data") if multi else ("data",),
        "cache_seq": ("data",),
        "q_heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
        "ssm_heads": "model",
        "ssm_inner": "model",
        "embed": None,
        "expert_embed": None,
        "layers": None,
        "seq": None,
    }
    if overrides:
        rules.update(overrides)
    return AxisRules(mesh=mesh, rules=rules, grad_sync=dict(grad_sync or {}))


def param_shardings(cfg: ModelConfig, rules: AxisRules):
    """NamedSharding tree matching abstract_params(cfg)."""
    axes = model_mod.param_axes(cfg)
    aparams = model_mod.abstract_params(cfg)

    def mk(ax, spec):
        with axis_rules(rules):
            return NamedSharding(rules.mesh, logical_spec(ax, shape=spec.shape))
    return jax.tree.map(mk, axes, aparams,
                        is_leaf=lambda x: isinstance(x, tuple))


def batch_shardings(cfg: ModelConfig, shape: InputShape, rules: AxisRules):
    specs = model_mod.input_specs(cfg, shape)
    out = {}
    with axis_rules(rules):
        for k, v in specs.items():
            ax = ("batch",) + (None,) * (len(v.shape) - 1)
            out[k] = NamedSharding(rules.mesh, logical_spec(ax, shape=v.shape))
    return out


def cache_shardings(cfg: ModelConfig, shape: InputShape, rules: AxisRules):
    specs = model_mod.cache_specs(cfg, shape.global_batch, shape.seq_len)
    axes = model_mod.cache_axes(cfg)

    def mk(ax, spec):
        with axis_rules(rules):
            return NamedSharding(rules.mesh, logical_spec(ax, shape=spec.shape))
    return jax.tree.map(mk, axes, specs, is_leaf=lambda x: isinstance(x, tuple))


def instrument_step(step_fn, timer):
    """Telemetry seam for the step builders: drop-in wrap of a jitted
    step with a ``repro.runtime.telemetry.StepTimer`` (see its docs)."""
    return timer.wrap(step_fn)


@dataclass(frozen=True)
class StepOptions:
    remat: bool = True
    loss_chunk: int = 0
    clip_norm: float = 1.0
    remat_policy: str = "full"


def make_train_step(cfg: ModelConfig, opt: AdamW, rules: AxisRules,
                    options: StepOptions | None = None):
    options = options if options is not None else StepOptions()

    def train_step(params, opt_state, step, batch):
        with axis_rules(rules):
            def loss(p):
                l, m = model_mod.loss_fn(
                    cfg, p, batch, remat=options.remat,
                    loss_chunk=options.loss_chunk,
                    remat_policy=options.remat_policy)
                return l, m
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
            grads, gnorm = clip_by_global_norm(grads, options.clip_norm)
            params, opt_state = opt.update(params, opt_state, grads, step)
            metrics = dict(metrics, loss=l, grad_norm=gnorm)
        return params, opt_state, metrics
    return train_step


def make_pipeline_train_step(opt: AdamW, runner,
                             options: StepOptions | None = None):
    """Train-step builder for the pipeline execution engines.

    ``runner`` is a ``repro.exec.engine.PipelineRunner`` or
    ``CompiledPipelineRunner`` — both satisfy the same
    ``step() -> (grads_list, StepStats)`` contract; params/opt
    state are per-stage lists committed to the stage devices. The
    optimizer update runs per stage (jitted once per stage, computation
    stays on the stage's devices); gradient clipping is by the GLOBAL
    norm across stages — per-stage squared norms are tiny scalars, so
    the cross-stage reduction happens on host like a real multi-host
    trainer's scalar allreduce. Each squared norm's dispatch is a
    ``step.grad_sqnorm`` span and its read a ``pipeline.sync`` span;
    each stage's update is a ``step.optimizer`` span.
    """
    from repro.obs.spans import span
    from repro.optim.adam import global_norm

    options = options if options is not None else StepOptions()

    def grad_sqnorm(g):
        with jax.named_scope("optimizer"):
            return global_norm(g) ** 2

    def adamw_update(p, s, g, step, scale):
        with jax.named_scope("optimizer"):
            g = jax.tree.map(lambda gg: (gg.astype(jnp.float32)
                                         * scale).astype(gg.dtype), g)
        return opt.update(p, s, g, step)

    sq = jax.jit(grad_sqnorm)
    upd = jax.jit(adamw_update)

    def step_fn(params_list, opt_state_list, step, batch, *,
                record: bool = False):
        grads, stats = runner.step(params_list, batch, record=record)
        k = runner.steps_run
        sqnorms = []
        for u, g in enumerate(grads):
            tags = {"step": k, "stage": u, "devices": runner.device_ids(u)}
            with span("step.grad_sqnorm", "pipeline", program="grad_sqnorm",
                      **tags):
                x = sq(g)
            with span("pipeline.sync", "pipeline", what="grad_norm", **tags):
                sqnorms.append(float(x))
        gnorm = float(sum(sqnorms)) ** 0.5
        scale = jnp.asarray(min(1.0, options.clip_norm / max(gnorm, 1e-9)),
                            jnp.float32)
        new_p, new_s = [], []
        for u, (p, s, g) in enumerate(zip(params_list, opt_state_list,
                                          grads, strict=True)):
            with span("step.optimizer", "pipeline", program="adamw_update",
                      step=k, stage=u, devices=runner.device_ids(u)):
                p2, s2 = upd(p, s, g, step, scale)
            new_p.append(p2)
            new_s.append(s2)
        metrics = dict(stats.metrics, loss=stats.loss, grad_norm=gnorm,
                       wall_time=stats.wall_time,
                       peak_stash=stats.peak_stash)
        return new_p, new_s, metrics
    return step_fn


def make_prefill_step(cfg: ModelConfig, rules: AxisRules):
    def prefill(params, batch):
        with axis_rules(rules):
            return model_mod.prefill_step(cfg, params, batch)
    return prefill


def make_serve_step(cfg: ModelConfig, rules: AxisRules):
    """One decode step: greedy next token + updated cache."""
    def serve(params, cache, tokens, pos):
        with axis_rules(rules):
            logits, cache = model_mod.decode_step(cfg, params, cache, tokens, pos)
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache
    return serve


def jit_train_step(cfg, opt, rules, shape,
                   options: StepOptions | None = None):
    options = options if options is not None else StepOptions()
    ps = param_shardings(cfg, rules)
    bs = batch_shardings(cfg, shape, rules)
    os_ = jax.tree.map(lambda s: s, ps)  # opt moments follow params
    opt_sh = {"mu": os_, "nu": os_}
    fn = make_train_step(cfg, opt, rules, options)
    return jax.jit(
        fn,
        in_shardings=(ps, opt_sh, NamedSharding(rules.mesh, P()), bs),
        out_shardings=(ps, opt_sh, NamedSharding(rules.mesh, P())),
    ), ps, opt_sh, bs


def jit_serve_step(cfg, rules, shape):
    ps = param_shardings(cfg, rules)
    cs = cache_shardings(cfg, shape, rules)
    bs = batch_shardings(cfg, shape, rules)
    fn = make_serve_step(cfg, rules)
    rep = NamedSharding(rules.mesh, P())
    return jax.jit(
        fn,
        in_shardings=(ps, cs, bs["tokens"], rep),
        out_shardings=(bs["tokens"], cs),
    ), ps, cs, bs


def jit_prefill_step(cfg, rules, shape):
    ps = param_shardings(cfg, rules)
    bs = batch_shardings(cfg, shape, rules)
    fn = make_prefill_step(cfg, rules)
    return jax.jit(fn, in_shardings=(ps, bs)), ps, None, bs
