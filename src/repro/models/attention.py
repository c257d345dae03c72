"""GQA attention for train/prefill (the splash flash kernel on a TPU, a
query-chunk scan in pure jnp elsewhere), sliding-window variant, and
single-token decode against a KV cache.

Shapes: q (B, S, H, hd); k/v (B, S, KV, hd). GQA groups G = H // KV.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import gqa_splash_attention, resolve_attn_impl
from repro.models.layers import ParamDef, rotary
from repro.parallel.sharding import current_rules, logical_shard, logical_spec

NEG_INF = -1e30
Q_CHUNK = 1024


def attn_defs(cfg) -> dict:
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


def _project_qkv(cfg, p, x, pos):
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
    k/v (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
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
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


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
    impl = resolve_attn_impl(cfg.attn_impl, S, cfg.sliding_window)
    batch_axis = _kernel_batch_axis(q, k) if impl == "pallas" else None
    if batch_axis is None and cfg.attn_impl != "pallas":
        o = chunk_attention(cfg, q, k, v, pos)
    else:
        o = _kernel_attention(cfg, q, k, v, batch_axis or ())
    out = logical_shard(o.reshape(B, S, -1), "batch", "seq", "q_heads")
    return out @ p["wo"], (k, v)


def init_kv_cache(cfg, batch: int, cache_len: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (batch, cache_len, KV, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def kv_cache_specs(cfg, batch: int, cache_len: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct((batch, cache_len, KV, hd), dtype)
    return {"k": s, "v": s}


KV_CACHE_AXES = ("batch", "cache_seq", "kv_heads", None)


def decode_attention(cfg, p, x, cache, pos):
    """One-token decode. x: (B, 1, D); cache k/v: (B, Sc, KV, hd) ring buffer
    (ring only engages when sliding_window > 0). ``pos``: scalar absolute
    position of the new token. Returns (out, new_cache)."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // KV
    q, k, v = _project_qkv(cfg, p, x, jnp.asarray(pos)[None])
    cache_len = cache["k"].shape[1]
    slot = pos % cache_len if cfg.sliding_window else pos
    new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    new_k = logical_shard(new_k, *KV_CACHE_AXES)
    new_v = logical_shard(new_v, *KV_CACHE_AXES)

    idx = jnp.arange(cache_len)
    valid = idx <= slot if not cfg.sliding_window else (
        (idx <= slot) | (pos >= cache_len))
    qg = q.reshape(B, 1, KV, G, hd)
    scale = hd ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, new_k) * scale
    s = jnp.where(valid[None, None, None, None], s.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(new_v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", w, new_v).reshape(B, 1, H * hd)
    return o @ p["wo"], {"k": new_k, "v": new_v}
