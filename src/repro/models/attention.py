"""GQA attention: chunked-causal (flash-style online softmax in pure jnp,
mirrored by kernels/flash_attention.py for TPU), sliding-window variant,
and single-token decode against a KV cache.

Shapes: q (B, S, H, hd); k/v (B, S, KV, hd). GQA groups G = H // KV.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, rotary
from repro.parallel.sharding import logical_shard

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


def attention(cfg, p, x, pos):
    """Full (or sliding-window) causal self-attention for train/prefill.

    Scans over query chunks so the (qc, S) score tile is the only softmax
    temp — the pure-jnp analogue of the Pallas flash kernel.
    Returns (out (B,S,D), (k, v) for cache use).
    """
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // KV
    q, k, v = _project_qkv(cfg, p, x, pos)
    if cfg.attn_impl == "pallas":
        # Pallas flash kernel: forward only (differentiating raises);
        # compiled Mosaic on a TPU, interpreted elsewhere.
        from repro.kernels.ops import gqa_flash_attention
        o = gqa_flash_attention(
            q, k, v, causal=True, window=cfg.sliding_window,
            block_q=min(128, S), block_k=min(128, S))
        out = o.reshape(B, S, H * hd)
        out = logical_shard(out, "batch", "seq", "q_heads")
        return out @ p["wo"], (k, v)
    qg = q.reshape(B, S, KV, G, hd)

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

    q_blocks = qg.reshape(B, n_chunks, qc, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), q_blocks))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H * hd)
    out = logical_shard(out, "batch", "seq", "q_heads")
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
