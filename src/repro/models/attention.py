"""GQA and latent (MLA) attention for train/prefill (the splash flash
kernel on a TPU, a query-chunk scan in pure jnp elsewhere), sliding-window
variant, and single-token decode against a KV cache (MLA: a cache of the
normed latent and the rotary key).

Shapes: q (B, S, H, hd); k/v (B, S, KV, hd). GQA groups G = H // KV.
MLA: q and k (B, S, H, nope + rope), v (B, S, H, v_head_dim).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import gqa_splash_attention, resolve_attn_impl
from repro.models.layers import ParamDef, rms_norm, rotary
from repro.parallel.sharding import current_rules, logical_shard, logical_spec

NEG_INF = -1e30
Q_CHUNK = 1024


def attn_defs(cfg) -> dict:
    if cfg.mla:
        return mla_defs(cfg)
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    defs = {
        "wq": ParamDef((D, H * hd), ("embed", "q_heads")),
        "wk": ParamDef((D, KV * hd), ("embed", "kv_heads")),
        "wv": ParamDef((D, KV * hd), ("embed", "kv_heads")),
        "wo": ParamDef((H * hd, D), ("q_heads", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H * hd,), ("q_heads",), init="zeros")
        defs["bk"] = ParamDef((KV * hd,), ("kv_heads",), init="zeros")
        defs["bv"] = ParamDef((KV * hd,), ("kv_heads",), init="zeros")
    return defs


def mla_defs(cfg) -> dict:
    """No query compression (q_lora_rank null): ``wq`` gives each head's
    [q_nope, q_pe]; ``wkv_a`` the latent and the shared rotary key;
    ``wkv_b`` each head's [k_nope, v] from the normed latent."""
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamDef((D, H * (nope + rope)), ("embed", "q_heads")),
        "wkv_a": ParamDef((D, r + rope), ("embed", None)),
        "kv_norm": ParamDef((r,), (None,), init="ones"),
        "wkv_b": ParamDef((r, H * (nope + vd)), (None, "q_heads")),
        "wo": ParamDef((H * vd, D), ("q_heads", "embed")),
    }


def _mla_latent(cfg, p, x, pos):
    """-> q (B, S, H, nope + rope), the normed latent (B, S, r) and the
    rotary key (B, S, rope), which is all a decode cache keeps."""
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    q = (x @ p["wq"]).reshape(B, S, H, cfg.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, rotary(q_pe, pos, cfg.rope_theta)], -1)
    ckv, k_pe = jnp.split(x @ p["wkv_a"], [r], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_pe = rotary(k_pe[:, :, None], pos, cfg.rope_theta)[:, :, 0]
    return q, ckv, k_pe


def _mla_kv(cfg, p, ckv, k_pe):
    """Per-head k = [k_nope, k_pe] and v from the latent cache."""
    B, S, _ = ckv.shape
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    kv = (ckv @ p["wkv_b"]).reshape(B, S, H, nope + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    k_pe = jnp.broadcast_to(k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))
    return jnp.concatenate([k_nope, k_pe], -1), v


def _project_mla(cfg, p, x, pos):
    q, ckv, k_pe = _mla_latent(cfg, p, x, pos)
    k, v = _mla_kv(cfg, p, ckv, k_pe)
    q = logical_shard(q, "batch", "seq", "q_heads", None)
    k = logical_shard(k, "batch", "seq", "q_heads", None)
    v = logical_shard(v, "batch", "seq", "q_heads", None)
    return q, k, v


def _project_qkv(cfg, p, x, pos):
    if cfg.mla:
        return _project_mla(cfg, p, x, pos)
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    q = rotary(q, pos, cfg.rope_theta)
    k = rotary(k, pos, cfg.rope_theta)
    q = logical_shard(q, "batch", "seq", "q_heads", None)
    k = logical_shard(k, "batch", "seq", "kv_heads", None)
    v = logical_shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa_chunk(q, k, v, mask):
    """q: (B, qc, KV, G, hd); k/v: (B, S, KV, hd); mask: (qc, S)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k) * scale
    s = jnp.where(mask[None, None, None], s.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v)


def chunk_attention(cfg, q, k, v, pos):
    """The jnp path: a scan over query chunks, each against all keys, so
    the (qc, S) score tile is the only softmax temp. q (B, S, H, hd),
    k (B, S, KV, hd), v (B, S, KV, vd) -> (B, S, H, vd)."""
    B, S, H, hd = q.shape
    KV, vd = k.shape[2], v.shape[-1]
    qg = q.reshape(B, S, KV, H // KV, hd)
    qc = min(cfg.attn_chunk or Q_CHUNK, S)
    assert S % qc == 0
    n_chunks = S // qc
    kpos = jnp.asarray(pos)

    def body(carry, inputs):
        i, q_blk = inputs
        qpos = i * qc + jnp.arange(qc)
        causal = kpos[None, :] <= qpos[:, None]
        if cfg.sliding_window:
            causal &= kpos[None, :] > qpos[:, None] - cfg.sliding_window
        o = _sdpa_chunk(q_blk, k, v, causal)
        return carry, o

    q_blocks = qg.reshape(B, n_chunks, qc, KV, H // KV, hd).transpose(
        1, 0, 2, 3, 4, 5)
    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), q_blocks))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, vd)


def _kernel_batch_axis(q, k):
    """How the active sharding rules let the kernel run: ``()`` where
    nothing is sharded, the batch's mesh axis where only the batch is,
    None where heads or the sequence are sharded."""
    r = current_rules()
    if r is None or r.mesh is None:
        return ()
    specs = (logical_spec(("batch", "seq", "q_heads", None), q.shape),
             logical_spec(("batch", "seq", "kv_heads", None), k.shape))

    def split(ax):
        return ax is not None and r.axis_size(ax) > 1
    if any(split(ax) for spec in specs for ax in tuple(spec)[1:]):
        return None
    batch = tuple(specs[0])[:1]
    return batch[0] if batch and split(batch[0]) else ()


def _kernel_attention(cfg, q, k, v, batch_axis):
    """The splash kernel, per batch shard under ``shard_map`` where
    ``batch_axis`` names the mesh axis the batch is sharded over. Shapes
    as ``chunk_attention``."""
    def call(q, k, v):
        return gqa_splash_attention(q, k, v, window=cfg.sliding_window)
    if not batch_axis:
        return call(q, k, v)
    spec = P(batch_axis)
    return jax.shard_map(call, mesh=current_rules().mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


def attention(cfg, p, x, pos):
    """Full (or sliding-window) causal self-attention for train/prefill.

    ``cfg.attn_impl`` "auto" resolves (``kernels.ops.resolve_attn_impl``)
    to the splash flash kernel, forward and backward, on a TPU for full
    causal attention at a sequence its block table tiles, unless heads
    or the sequence are sharded; to the jnp query-chunk scan everywhere
    else. "pallas" and "jnp" force one path. ``pos`` is 0..S-1.
    Returns (out (B,S,D), (k, v) for cache use).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, pos)
    impl = resolve_attn_impl(cfg.attn_impl, S, cfg.sliding_window,
                             q.shape[-1])
    batch_axis = _kernel_batch_axis(q, k) if impl == "pallas" else None
    if batch_axis is None and cfg.attn_impl != "pallas":
        o = chunk_attention(cfg, q, k, v, pos)
    else:
        o = _kernel_attention(cfg, q, k, v, batch_axis or ())
    out = logical_shard(o.reshape(B, S, -1), "batch", "seq", "q_heads")
    return out @ p["wo"], (k, v)


def kv_cache_specs(cfg, batch: int, cache_len: int, dtype):
    if cfg.mla:
        return {"ckv": jax.ShapeDtypeStruct(
                    (batch, cache_len, cfg.kv_lora_rank), dtype),
                "k_pe": jax.ShapeDtypeStruct(
                    (batch, cache_len, cfg.qk_rope_head_dim), dtype)}
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct((batch, cache_len, KV, hd), dtype)
    return {"k": s, "v": s}


KV_CACHE_AXES = ("batch", "cache_seq", "kv_heads", None)
LATENT_CACHE_AXES = ("batch", "cache_seq", None)


def kv_cache_axes(cfg) -> dict:
    if cfg.mla:
        return {"ckv": LATENT_CACHE_AXES, "k_pe": LATENT_CACHE_AXES}
    return {"k": KV_CACHE_AXES, "v": KV_CACHE_AXES}


def _decode_sdpa(q, k, v, valid):
    """q (B, 1, H, hd); k (B, Sc, KV, hd), v (B, Sc, KV, vd); valid (Sc,)
    -> (B, 1, H * vd)."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, 1, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) * hd ** -0.5
    s = jnp.where(valid[None, None, None, None], s.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v).reshape(B, 1, -1)


def _write(cache, new, slot, axes):
    out = jax.lax.dynamic_update_slice_in_dim(cache, new, slot, axis=1)
    return logical_shard(out, *axes)


def decode_attention(cfg, p, x, cache, pos):
    """One-token decode. x: (B, 1, D); cache k/v: (B, Sc, KV, hd) ring buffer
    (ring only engages when sliding_window > 0); under MLA the normed
    latent (B, Sc, r) and rotary key (B, Sc, rope), from which each step
    expands k and v. ``pos``: scalar absolute position of the new token.
    Returns (out, new_cache)."""
    pos_v = jnp.asarray(pos)[None]
    cache_len = jax.tree.leaves(cache)[0].shape[1]
    slot = pos % cache_len if cfg.sliding_window else pos
    idx = jnp.arange(cache_len)
    valid = idx <= slot if not cfg.sliding_window else (
        (idx <= slot) | (pos >= cache_len))
    if cfg.mla:
        q, ckv, k_pe = _mla_latent(cfg, p, x, pos_v)
        new = {"ckv": _write(cache["ckv"], ckv, slot, LATENT_CACHE_AXES),
               "k_pe": _write(cache["k_pe"], k_pe, slot, LATENT_CACHE_AXES)}
        k, v = _mla_kv(cfg, p, new["ckv"], new["k_pe"])
    else:
        q, k, v = _project_qkv(cfg, p, x, pos_v)
        new = {"k": _write(cache["k"], k, slot, KV_CACHE_AXES),
               "v": _write(cache["v"], v, slot, KV_CACHE_AXES)}
        k, v = new["k"], new["v"]
    return _decode_sdpa(q, k, v, valid) @ p["wo"], new
