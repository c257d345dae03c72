"""Model adapter: cut a ``ModelConfig`` LM into pipeline stage functions.

The decoder stack is a scan over ``num_periods`` period-params (leading
dim of every leaf under ``params["blocks"]``), so a stage is a contiguous
period span plus the edges: stage 0 owns the embedding (+ frontend
projection), the last stage owns the final norm, head, and loss.

Stage functions share one signature the engine understands:

    fn(stage_params, carry, mb) -> carry            (stages 0..S-2)
    fn(stage_params, carry, mb) -> (loss, metrics)  (last stage)

``carry`` is ``(hidden (B, S, D), aux (B,))`` — the MoE aux loss rides
along as a per-example vector so it batch-shards with the activations
(per-stage data parallelism splits the microbatch across the stage's
submesh; a scalar aux could not be sharded, and a cross-shard mean inside
the differentiated body would force a collective the engine's explicit
AR/PS/SFB gradient sync must stay in charge of).

Tied embeddings: the head weight IS the embedding matrix, which lives on
stage 0. ``split_model`` then omits the head from the last stage's
params; the engine broadcasts the embedding to the last stage each step
(``tied_ref``) and folds the head gradient back into the embedding
gradient — the same two boundary transfers a real pipeline runtime pays
for weight tying.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.models import transformer as tf_mod
from repro.models.layers import cross_entropy, rms_norm

TIED_HEAD = "tied_head"      # engine-injected key on the last stage


def _first_stage(cfg: ModelConfig):
    def fn(p, carry, mb):
        del carry
        x, pos, n_prefix = model_mod._embed_inputs(cfg, p, mb)
        del n_prefix
        aux = jnp.zeros((x.shape[0],), jnp.float32)
        return _run_blocks(cfg, p, x, aux)
    return fn


def _mid_stage(cfg: ModelConfig):
    def fn(p, carry, mb):
        del mb
        x, aux = carry
        return _run_blocks(cfg, p, x, aux)
    return fn


def _run_blocks(cfg, p, x, aux):
    blocks = p.get("blocks")
    if blocks is not None and jax.tree.leaves(blocks):
        pos = jnp.arange(x.shape[1])
        x, a = tf_mod.stack_fwd(cfg, blocks, x, pos, remat=False)
        aux = aux + a                   # scalar broadcasts over (B,)
    return x, aux


def _last_stage(cfg: ModelConfig, tied: bool):
    def fn(p, carry, mb):
        x, aux = carry
        x, aux = _run_blocks(cfg, p, x, aux)
        with jax.named_scope("head_ce"):
            h = rms_norm(x, p["final_norm"], cfg.norm_eps)
            n_prefix = h.shape[1] - mb["labels"].shape[1]
            if n_prefix:
                h = h[:, n_prefix:]
            w = p[TIED_HEAD] if tied else p["head"]
            ce = cross_entropy(h @ w.T if tied else h @ w, mb["labels"])
            loss = ce + model_mod.MAX_SMOKE_AUX * jnp.mean(aux)
        return loss, {"ce": ce, "aux": jnp.mean(aux)}
    return fn


def split_model(cfg: ModelConfig, params, n_stages: int,
                splits: list | None = None):
    """-> (stage_params, stage_fns, mb_keys, tied_ref).

    ``splits`` is the per-stage [lo, hi) period span (default: equal
    chunks; pass ``StagePlan.layer_splits(cfg.num_periods)`` for the
    capacity-aware cut). ``mb_keys[s]`` names the microbatch entries
    stage ``s`` consumes. ``tied_ref`` is ``("embed", TIED_HEAD)`` when
    the head is tied to the stage-0 embedding, else ``None``.
    """
    P = cfg.num_periods
    if splits is None:
        splits = [(s * P // n_stages, (s + 1) * P // n_stages)
                  for s in range(n_stages)]
    assert len(splits) == n_stages and splits[0][0] == 0 \
        and splits[-1][1] == P, splits

    tied = cfg.tie_embeddings
    stage_params, stage_fns, mb_keys = [], [], []
    for s, (lo, hi) in enumerate(splits):
        p = {"blocks": jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi],
                                    params["blocks"])}
        keys: list = []
        if s == 0:
            p["embed"] = params["embed"]
            keys.append("tokens")
            if cfg.frontend != "none":
                p["frontend_proj"] = params["frontend_proj"]
                keys.append("prefix")
            fn = _first_stage(cfg)
        else:
            fn = _mid_stage(cfg)
        if s == n_stages - 1:
            p["final_norm"] = params["final_norm"]
            if not tied:
                p["head"] = params["head"]
            keys.append("labels")
            fn = _last_stage(cfg, tied) if s > 0 else \
                _single_stage(cfg, tied)
        stage_params.append(p)
        stage_fns.append(fn)
        mb_keys.append(keys)
    tied_ref = ("embed", TIED_HEAD) if tied and n_stages > 1 else None
    return stage_params, stage_fns, mb_keys, tied_ref


def _single_stage(cfg: ModelConfig, tied: bool):
    """Degenerate 1-stage pipeline (embed + blocks + head in one)."""
    first, last = _first_stage(cfg), _last_stage(cfg, tied=False)

    def fn(p, carry, mb):
        carry = first(p, carry, mb)
        # first() already ran the decoder blocks; hand last() a
        # blocks-free view so it only applies norm + head + loss
        p_last = {k: v for k, v in p.items() if k != "blocks"}
        if tied:
            p_last["head"] = p["embed"].T
        return last(p_last, carry, mb)
    return fn
