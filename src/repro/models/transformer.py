"""Decoder stack: repeating layer *periods* (cfg.pattern) scanned with
``jax.lax.scan`` so HLO size is O(period), not O(depth) — required for the
61-layer Kimi config under a CPU compile budget and the right production
choice regardless.

Each layer = mixer ('A' attention / 'M' mamba) + optional FFN
(dense SwiGLU or MoE per cfg.moe_every). Each sub-block, its pre-norm
included, runs under a ``jax.named_scope`` (``attention``, ``ssd``,
``mlp``, ``moe``), so the compiled program's op metadata, and the device
trace through it, says which block an op belongs to.

``cfg.first_dense_layers`` leading layers (attention + a dense FFN of
``cfg.dense_ff``) differ from the period: they are a stack of their own
(``lead_defs``), scanned before the periods under the same remat and
scopes. Layers return MoE statistics beside x: the load-balance loss
``aux`` and, for the dropless layer, its counters (``moe.STAT_MERGE``).
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


def lead_config(cfg):
    """The leading layers as a one-layer period of their own."""
    return cfg.replace(num_layers=cfg.first_dense_layers, first_dense_layers=0,
                       layer_pattern="A", num_experts=0, d_ff=cfg.dense_ff)


def lead_defs(cfg) -> dict:
    return stacked_defs(lead_config(cfg))


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


def no_stats(cfg) -> dict:
    """MoE statistics of a stack with no layer yet."""
    return moe_mod.zero_stats(cfg if any(
        _layer_is_moe(cfg, j) for j in range(len(cfg.pattern))) else None)


def merge_stats(a: dict, b: dict) -> dict:
    return {k: moe_mod.STAT_MERGE[k](a[k], b[k]) for k in a}


def _ffn_fwd(cfg, p, h, j: int):
    """-> (y, MoE statistics of this layer or None)."""
    if not _layer_is_moe(cfg, j):
        return mlp_fwd(p, h), None
    if cfg.dropless:
        return moe_mod.dropless_fwd(cfg, p, h)
    y, aux = moe_mod.moe_fwd(cfg, p, h)
    return y, {"aux": aux}


def _layer_fwd(cfg, lp, x, pos, j: int, ch: str):
    """Full-sequence layer. Returns (x, MoE statistics or None)."""
    with jax.named_scope(_mixer_scope(ch)):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        if ch == "A":
            mix, _ = attn.attention(cfg, lp["mixer"], h, pos)
        else:
            mix, _ = ssm_mod.mamba_fwd(cfg, lp["mixer"], h)
    x = x + mix
    stats = None
    if cfg.d_ff > 0:
        with jax.named_scope(_ffn_scope(cfg, j)):
            h = rms_norm(x, lp["norm2"], cfg.norm_eps)
            y, stats = _ffn_fwd(cfg, lp["ffn"], h, j)
        x = x + y
    return logical_shard(x, "batch", "seq", "embed"), stats


def period_fwd(cfg, rules_fp, pparams, x, pos):
    """``rules_fp`` is the static fingerprint of the active sharding rules
    (see parallel.sharding.rules_fingerprint) — it keeps jax.checkpoint's
    trace cache honest when the same config is lowered under different
    rules in one process."""
    del rules_fp
    stats = no_stats(cfg)
    for j, ch in enumerate(cfg.pattern):
        x, s = _layer_fwd(cfg, pparams[f"layer{j}"], x, pos, j, ch)
        if s is not None:
            stats = merge_stats(stats, s)
    return x, stats


REMAT_POLICIES = {
    "full": None,   # save only the scan carry (recompute everything)
    "dots": "dots_with_no_batch_dims_saveable",
    "none": "everything_saveable",
}


def stack_fwd(cfg, stacked, x, pos, remat: bool = True,
              remat_policy: str = "full"):
    """x: (B, S, D) -> (x, MoE statistics over the stack: ``aux`` and the
    dropless layer's counters). ``stacked``: period params with a leading
    num_periods dim. ``remat_policy`` picks what the checkpoint saves (a
    §Perf lever: recompute-vs-HBM-traffic trade)."""
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
        x, stats = carry
        x, s = fn(cfg, fp, pparams, x, pos)
        return (x, merge_stats(stats, s)), None

    (x, stats), _ = jax.lax.scan(body, (x, no_stats(cfg)), stacked)
    return x, stats


def layers_fwd(cfg, params, x, pos, remat: bool = True,
               remat_policy: str = "full"):
    """The leading layers (``params["lead"]``, where the config has them)
    and then the periods (``params["blocks"]``) -> (x, MoE statistics)."""
    if "lead" in params:
        x, _ = stack_fwd(lead_config(cfg), params["lead"], x, pos,
                         remat=remat, remat_policy=remat_policy)
    return stack_fwd(cfg, params["blocks"], x, pos, remat=remat,
                     remat_policy=remat_policy)


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


def _stacks(cfg) -> dict:
    """The scanned stacks by params key, each with its own config."""
    out = {"blocks": cfg}
    if cfg.first_dense_layers:
        out["lead"] = lead_config(cfg)
    return out


def layers_cache_specs(cfg, batch: int, cache_len: int, dtype):
    return {k: stacked_cache_specs(c, batch, cache_len, dtype)
            for k, c in _stacks(cfg).items()}


def init_layers_cache(cfg, batch: int, cache_len: int, dtype):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        layers_cache_specs(cfg, batch, cache_len, dtype))


def _stack_cache_axes(cfg):
    axes = {}
    for j, ch in enumerate(cfg.pattern):
        if ch == "A":
            axes[f"layer{j}"] = attn.kv_cache_axes(cfg)
        else:
            axes[f"layer{j}"] = dict(ssm_mod.SSM_CACHE_AXES)
    return jax.tree.map(lambda a: ("layers", *a), axes,
                        is_leaf=lambda x: isinstance(x, tuple))


def cache_axes(cfg):
    return {k: _stack_cache_axes(c) for k, c in _stacks(cfg).items()}


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
            y, _ = _ffn_fwd(cfg, lp["ffn"], h, j)
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


def layers_decode(cfg, params, cache, x, pos):
    """One token through the leading layers, then the periods."""
    new = {}
    if "lead" in cache:
        x, new["lead"] = stack_decode(lead_config(cfg), params["lead"],
                                      cache["lead"], x, pos)
    x, new["blocks"] = stack_decode(cfg, params["blocks"], cache["blocks"],
                                    x, pos)
    return x, new
