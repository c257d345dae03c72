"""Decoder stack: repeating layer *periods* (cfg.pattern) scanned with
``jax.lax.scan`` so HLO size is O(period), not O(depth) — required for the
61-layer Kimi config under a CPU compile budget and the right production
choice regardless.

Each layer = mixer ('A' attention / 'M' mamba) + optional FFN
(dense SwiGLU or MoE per cfg.moe_every). Each sub-block, its pre-norm
included, runs under a ``jax.named_scope`` (``attention``, ``ssd``,
``mlp``, ``moe``), so the compiled program's op metadata, and the device
trace through it, says which block an op belongs to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamDef, mlp_defs, mlp_fwd, rms_norm, stack_defs)
from repro.parallel.sharding import logical_shard


def _layer_is_moe(cfg, j: int) -> bool:
    return (cfg.num_experts > 0 and cfg.d_ff > 0
            and j % cfg.moe_every == cfg.moe_every - 1)


def layer_defs(cfg, j: int, ch: str) -> dict:
    D = cfg.d_model
    defs = {"norm1": ParamDef((D,), ("embed",), init="ones")}
    if ch == "A":
        defs["mixer"] = attn.attn_defs(cfg)
    else:
        defs["mixer"] = ssm_mod.ssm_defs(cfg)
    if cfg.d_ff > 0:
        defs["norm2"] = ParamDef((D,), ("embed",), init="ones")
        if _layer_is_moe(cfg, j):
            defs["ffn"] = moe_mod.moe_defs(cfg)
        else:
            defs["ffn"] = mlp_defs(D, cfg.d_ff)
    return defs


def period_defs(cfg) -> dict:
    return {f"layer{j}": layer_defs(cfg, j, ch)
            for j, ch in enumerate(cfg.pattern)}


def stacked_defs(cfg) -> dict:
    return stack_defs(period_defs(cfg), cfg.num_periods)


# --------------------------------------------------------------- forward

def _mixer_scope(ch: str) -> str:
    return "attention" if ch == "A" else "ssd"


def _ffn_scope(cfg, j: int) -> str:
    return "moe" if _layer_is_moe(cfg, j) else "mlp"


def _layer_fwd(cfg, lp, x, pos, j: int, ch: str):
    """Full-sequence layer. Returns (x, aux_loss)."""
    with jax.named_scope(_mixer_scope(ch)):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        if ch == "A":
            mix, _ = attn.attention(cfg, lp["mixer"], h, pos)
        else:
            mix, _ = ssm_mod.mamba_fwd(cfg, lp["mixer"], h)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if cfg.d_ff > 0:
        with jax.named_scope(_ffn_scope(cfg, j)):
            h = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if _layer_is_moe(cfg, j):
                y, aux = moe_mod.moe_fwd(cfg, lp["ffn"], h)
            else:
                y = mlp_fwd(lp["ffn"], h)
        x = x + y
    return logical_shard(x, "batch", "seq", "embed"), aux


def period_fwd(cfg, rules_fp, pparams, x, pos):
    """``rules_fp`` is the static fingerprint of the active sharding rules
    (see parallel.sharding.rules_fingerprint) — it keeps jax.checkpoint's
    trace cache honest when the same config is lowered under different
    rules in one process."""
    del rules_fp
    aux = jnp.zeros((), jnp.float32)
    for j, ch in enumerate(cfg.pattern):
        x, a = _layer_fwd(cfg, pparams[f"layer{j}"], x, pos, j, ch)
        aux = aux + a
    return x, aux


REMAT_POLICIES = {
    "full": None,   # save only the scan carry (recompute everything)
    "dots": "dots_with_no_batch_dims_saveable",
    "none": "everything_saveable",
}


def stack_fwd(cfg, stacked, x, pos, remat: bool = True,
              remat_policy: str = "full"):
    """x: (B, S, D) -> (x, total_aux). ``stacked``: period params with a
    leading num_periods dim. ``remat_policy`` picks what the checkpoint
    saves (a §Perf lever: recompute-vs-HBM-traffic trade)."""
    from repro.parallel.sharding import rules_fingerprint
    fp = rules_fingerprint()
    fn = period_fwd
    if remat:
        pol_name = REMAT_POLICIES.get(remat_policy)
        policy = getattr(jax.checkpoint_policies, pol_name) \
            if pol_name else None
        fn = jax.checkpoint(period_fwd, static_argnums=(0, 1),
                            policy=policy)

    def body(carry, pparams):
        x, aux = carry
        x, a = fn(cfg, fp, pparams, x, pos)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# --------------------------------------------------------------- decode

def layer_cache_specs(cfg, j: int, ch: str, batch: int, cache_len: int, dtype):
    if ch == "A":
        return attn.kv_cache_specs(cfg, batch, cache_len, dtype)
    return ssm_mod.ssm_cache_specs(cfg, batch, dtype)


def period_cache_specs(cfg, batch: int, cache_len: int, dtype):
    return {f"layer{j}": layer_cache_specs(cfg, j, ch, batch, cache_len, dtype)
            for j, ch in enumerate(cfg.pattern)}


def stacked_cache_specs(cfg, batch: int, cache_len: int, dtype):
    per = period_cache_specs(cfg, batch, cache_len, dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.num_periods, *s.shape), s.dtype), per)


def init_stacked_cache(cfg, batch: int, cache_len: int, dtype):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        stacked_cache_specs(cfg, batch, cache_len, dtype))


def cache_axes(cfg):
    axes = {}
    for j, ch in enumerate(cfg.pattern):
        if ch == "A":
            axes[f"layer{j}"] = {"k": attn.KV_CACHE_AXES, "v": attn.KV_CACHE_AXES}
        else:
            axes[f"layer{j}"] = dict(ssm_mod.SSM_CACHE_AXES)
    return jax.tree.map(lambda a: ("layers", *a), axes,
                        is_leaf=lambda x: isinstance(x, tuple))


def _layer_decode(cfg, lp, lcache, x, pos, j: int, ch: str):
    with jax.named_scope(_mixer_scope(ch)):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        if ch == "A":
            mix, new_cache = attn.decode_attention(cfg, lp["mixer"], h, lcache, pos)
        else:
            mix, new_cache = ssm_mod.mamba_decode(cfg, lp["mixer"], h, lcache)
    x = x + mix
    if cfg.d_ff > 0:
        with jax.named_scope(_ffn_scope(cfg, j)):
            h = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if _layer_is_moe(cfg, j):
                y, _ = moe_mod.moe_fwd(cfg, lp["ffn"], h)
            else:
                y = mlp_fwd(lp["ffn"], h)
        x = x + y
    return x, new_cache


def period_decode(cfg, pparams, pcache, x, pos):
    new = {}
    for j, ch in enumerate(cfg.pattern):
        x, new[f"layer{j}"] = _layer_decode(
            cfg, pparams[f"layer{j}"], pcache[f"layer{j}"], x, pos, j, ch)
    return x, new


def stack_decode(cfg, stacked, cache, x, pos):
    def body(x, inp):
        pparams, pcache = inp
        x, new_pcache = period_decode(cfg, pparams, pcache, x, pos)
        return x, new_pcache

    x, new_cache = jax.lax.scan(body, x, (stacked, cache))
    return x, new_cache
