"""Top-level LM: embeddings, decoder stack, head, loss, decode steps, and
``input_specs`` (ShapeDtypeStruct stand-ins for the dry-run).

Audio/VLM frontends are stubs per the brief: ``input_specs`` provides
precomputed frame/patch embeddings ("prefix") of shape
(B, cfg.frontend_tokens, D); the decoder consumes them as a prefix and the
loss covers token positions only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import transformer as tf_mod
from repro.models.layers import (
    ParamDef, abstract_tree, axes_tree, cross_entropy, init_tree, rms_norm)
from repro.parallel.sharding import logical_shard

MAX_SMOKE_AUX = 0.01  # aux-loss weight


def model_defs(cfg: ModelConfig) -> dict:
    D, V = cfg.d_model, cfg.vocab_size
    defs = {
        "embed": ParamDef((V, D), ("vocab", "embed"), scale=0.02),
        "blocks": tf_mod.stacked_defs(cfg),
        "final_norm": ParamDef((D,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((D, V), ("embed", "vocab"), scale=0.02)
    if cfg.frontend != "none":
        # learned projection applied to the (stubbed) frontend embeddings
        defs["frontend_proj"] = ParamDef((D, D), ("embed", "embed"))
    return defs


def init_params(cfg: ModelConfig, key):
    return init_tree(model_defs(cfg), key, jnp.dtype(cfg.dtype))


def abstract_params(cfg: ModelConfig):
    return abstract_tree(model_defs(cfg), jnp.dtype(cfg.dtype))


def param_axes(cfg: ModelConfig):
    return axes_tree(model_defs(cfg))


def _head(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return h @ w


@jax.named_scope("embed")
def _embed_inputs(cfg, params, batch):
    """Token (+ prefix) embedding. Returns (x, pos, n_prefix)."""
    x = params["embed"][batch["tokens"]]
    x = x * (cfg.d_model ** 0.5)
    n_prefix = 0
    if cfg.frontend != "none":
        prefix = batch["prefix"].astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([prefix, x], axis=1)
        n_prefix = prefix.shape[1]
    x = logical_shard(x, "batch", "seq", "embed")
    pos = jnp.arange(x.shape[1])
    return x, pos, n_prefix


def forward(cfg: ModelConfig, params, batch, remat: bool = True,
            remat_policy: str = "full"):
    """Full-sequence forward. Returns (hidden (B, S, D), aux_loss, n_prefix)."""
    x, pos, n_prefix = _embed_inputs(cfg, params, batch)
    x, aux = tf_mod.stack_fwd(cfg, params["blocks"], x, pos, remat=remat,
                              remat_policy=remat_policy)
    with jax.named_scope("head_ce"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, n_prefix


@jax.named_scope("loss")
def loss_fn(cfg: ModelConfig, params, batch, remat: bool = True,
            loss_chunk: int = 0, remat_policy: str = "full"):
    """Mean next-token CE (+ MoE aux). ``loss_chunk`` > 0 computes logits
    in sequence chunks to avoid materializing (B, S, V). The final norm,
    head and CE run under the ``head_ce`` named scope. The whole loss is
    the ``loss`` scope, which a differentiating transform wraps
    (``jvp(loss)``), so the scopes inside it stay plain in op names."""
    h, aux, n_prefix = forward(cfg, params, batch, remat=remat,
                               remat_policy=remat_policy)
    with jax.named_scope("head_ce"):
        if n_prefix:
            h = h[:, n_prefix:]
        labels = batch["labels"]
        if loss_chunk and h.shape[1] % loss_chunk == 0 and h.shape[1] > loss_chunk:
            n = h.shape[1] // loss_chunk
            hc = h.reshape(h.shape[0], n, loss_chunk, -1).swapaxes(0, 1)
            lc = labels.reshape(labels.shape[0], n, loss_chunk).swapaxes(0, 1)

            def body(tot, inp):
                hb, lb = inp
                return tot + cross_entropy(_head(cfg, params, hb), lb), None

            tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
            ce = tot / n
        else:
            logits = _head(cfg, params, h)
            logits = logical_shard(logits, "batch", "seq", "vocab")
            ce = cross_entropy(logits, labels)
        return ce + MAX_SMOKE_AUX * aux, {"ce": ce, "aux": aux}


# ------------------------------------------------------------- decoding

def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return tf_mod.init_stacked_cache(
        cfg, batch, cache_len_for(cfg, seq_len), jnp.dtype(cfg.dtype))


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int):
    return tf_mod.stacked_cache_specs(
        cfg, batch, cache_len_for(cfg, seq_len), jnp.dtype(cfg.dtype))


def cache_axes(cfg: ModelConfig):
    return tf_mod.cache_axes(cfg)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: scalar absolute position.
    Returns (logits (B, 1, V), new_cache)."""
    x = params["embed"][tokens] * (cfg.d_model ** 0.5)
    x = logical_shard(x, "batch", None, "embed")
    x, new_cache = tf_mod.stack_decode(cfg, params["blocks"], cache, x, pos)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, x)
    return logits, new_cache


def prefill_step(cfg: ModelConfig, params, batch):
    """Inference prefill: full forward, returns last-position logits."""
    h, _, _ = forward(cfg, params, batch, remat=False)
    return _head(cfg, params, h[:, -1:])


# ----------------------------------------------------------- input specs

def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape
    (weak-type-correct, shardable, no device allocation)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype("int32")
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    n_tok = S - (cfg.frontend_tokens if cfg.frontend != "none" else 0)
    specs = {"tokens": jax.ShapeDtypeStruct((B, n_tok), i32)}
    if cfg.frontend != "none":
        specs["prefix"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), dt)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, n_tok), i32)
    return specs
