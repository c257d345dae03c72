"""Dense decoder family (Qwen2-style): GQA attention with QKV bias and
rotary positions, SwiGLU MLP, RMSNorm, tied or untied head.

Everything the benchmark needs of one architecture family, and nothing
of the program under test beyond its configuration class:

* ``program_config`` maps a configuration file onto the program's
  ``ModelConfig``;
* ``make_params`` draws the weights from a key, in the program's tree
  layout and the type they are trained in;
* ``flops_per_token`` is the model-FLOP count;
* ``ref_embed`` / ``ref_layer`` / ``head_weight`` are the plain float32
  reference (``bench/reference.py`` drives them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512        # reference attention: queries per block


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or (conf["hidden_size"]
                                    // conf["num_attention_heads"])


def program_config(conf: dict, layers: int | None = None):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=layers or conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], head_dim=head_dim(conf),
        qkv_bias=True, tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), dtype=conf["dtype"])


def make_params(conf: dict, key):
    """The program's parameter tree: matrices N(0, 0.02), biases 0,
    norm gains 1, each layer's leaves stacked on a leading axis."""
    D, F, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 head_dim(conf))
    L = conf["num_hidden_layers"]
    dt = jnp.dtype(conf["dtype"])
    ks = iter(jax.random.split(key, 9))

    def normal(shape):
        return (0.02 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    layer = {
        "norm1": jnp.ones((L, D), dt), "norm2": jnp.ones((L, D), dt),
        "mixer": {"wq": normal((L, D, H * hd)), "wk": normal((L, D, KV * hd)),
                  "wv": normal((L, D, KV * hd)), "wo": normal((L, H * hd, D)),
                  "bq": jnp.zeros((L, H * hd), dt),
                  "bk": jnp.zeros((L, KV * hd), dt),
                  "bv": jnp.zeros((L, KV * hd), dt)},
        "ffn": {"w_gate": normal((L, D, F)), "w_up": normal((L, D, F)),
                "w_down": normal((L, F, D))},
    }
    tree = {"embed": normal((V, D)), "blocks": {"layer0": layer},
            "final_norm": jnp.ones((D,), dt)}
    if not conf["tie_word_embeddings"]:
        tree["head"] = normal((D, V))
    return tree


def flops_per_token(conf: dict, seq: int) -> float:
    """Training FLOPs per token: 6 x the matmul parameters (the head
    included, the embedding lookup not) plus causal attention, whose
    score and value products cost 4 x heads x head_dim per
    (query, key) pair forward, x3 with the backward, over (seq + 1) / 2
    keys per query on average. Recomputation is not counted."""
    D, F, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 head_dim(conf))
    L = conf["num_hidden_layers"]
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    matmul = L * per_layer + V * D
    attention = L * 6 * H * hd * (seq + 1)
    return 6.0 * matmul + attention


# ------------------------------------------------------------ reference

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotary embedding, rotate-half form: x (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, mm):
    """Causal softmax attention, one block of queries at a time.
    q (B, S, H, hd); k, v (B, S, H, hd) with KV heads already repeated."""
    B, S, H, hd = q.shape
    qb = min(QUERY_BLOCK, S)
    blocks = q.reshape(B, S // qb, qb, H, hd).swapaxes(0, 1)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        i, qblk = args
        s = mm("bqhd,bkhd->bhqk", qblk, k) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, (jnp.arange(S // qb), blocks))
    return out.swapaxes(0, 1).reshape(B, S, H * hd)


def ref_embed(conf, edge, tokens):
    return edge["embed"][tokens] * conf["hidden_size"] ** 0.5


def ref_layer(conf, p, x, mm):
    """One decoder layer in float32; ``mm`` is the contraction (einsum)
    that the reference or its lower-precision control uses."""
    B, S, D = x.shape
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 head_dim(conf))
    eps = conf["rms_norm_eps"]
    a = p["mixer"]
    h = rms_norm(x, p["norm1"], eps)
    q = (mm("bsd,de->bse", h, a["wq"]) + a["bq"]).reshape(B, S, H, hd)
    k = (mm("bsd,de->bse", h, a["wk"]) + a["bk"]).reshape(B, S, KV, hd)
    v = (mm("bsd,de->bse", h, a["wv"]) + a["bv"]).reshape(B, S, KV, hd)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
    x = x + mm("bse,ed->bsd", _attention(q, k, v, mm), a["wo"])
    f = p["ffn"]
    h = rms_norm(x, p["norm2"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"]))
    u = mm("bsd,df->bsf", h, f["w_up"])
    return x + mm("bsf,fd->bsd", g * u, f["w_down"])


def ref_final_norm(conf, edge, x):
    return rms_norm(x, edge["final_norm"], conf["rms_norm_eps"])


def head_weight(conf, edge):
    """(V, D) output projection."""
    return edge["embed"] if conf["tie_word_embeddings"] else edge["head"].T
