"""Mamba-2 family (SSD, arXiv:2405.21060): attention-free stacks of
RMSNorm + Mamba-2 mixer with a residual, tied or untied head.

Same interface as ``bench/families/dense.py``. The reference SSD is the
paper's chunked "minimal SSD" at the published chunk of 256, written here
apart from the program's scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.families.dense import rms_norm

SSD_CHUNK = 256          # published chunk_size; fixes the FLOP formula


def padded_vocab(conf: dict) -> int:
    m = conf.get("pad_vocab_size_multiple", 1)
    return -(-conf["vocab_size"] // m) * m


def _sizes(conf):
    D = conf["d_model"]
    di = conf["expand"] * D
    nh = di // conf["headdim"]
    return D, di, nh, conf["headdim"], conf["d_state"], conf["ngroups"]


def program_config(conf: dict, layers: int | None = None):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="ssm",
        num_layers=layers or conf["n_layer"], d_model=conf["d_model"],
        num_heads=0, num_kv_heads=0, d_ff=conf["d_intermediate"],
        vocab_size=padded_vocab(conf), ssm_state=conf["d_state"],
        ssm_expand=conf["expand"], ssm_head_dim=conf["headdim"],
        ssm_conv=conf["d_conv"], ssm_ngroups=conf["ngroups"],
        ssm_chunk=conf["chunk_size"],
        layer_pattern="M", tie_embeddings=conf["tie_embeddings"],
        norm_eps=float(conf["norm_epsilon"]), dtype=conf["dtype"])


def make_params(conf: dict, key):
    """The program's parameter tree. Projections N(0, 0.02), the
    depthwise conv U(-1/sqrt(K), 1/sqrt(K)), and the published SSM
    initialisation: A in U[1, 16] (stored as log), dt in
    log-U[1e-3, 1e-1] (stored through the inverse softplus)."""
    D, di, nh, hd, ds, g = _sizes(conf)
    K, L, V = conf["d_conv"], conf["n_layer"], padded_vocab(conf)
    conv_ch = di + 2 * g * ds
    dt = jnp.dtype(conf["dtype"])
    ks = iter(jax.random.split(key, 8))

    def normal(shape):
        return (0.02 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    step = jnp.exp(uniform((L, nh), jnp.log(1e-3), jnp.log(1e-1)))
    mixer = {
        "in_proj": normal((L, D, 2 * di + 2 * g * ds + nh)),
        "conv_w": uniform((L, K, conv_ch), -K ** -0.5, K ** -0.5).astype(dt),
        "conv_b": jnp.zeros((L, conv_ch), dt),
        "A_log": jnp.log(uniform((L, nh), 1.0, 16.0)).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "D_skip": jnp.ones((L, nh), dt),
        "norm": jnp.ones((L, di), dt),
        "out_proj": normal((L, di, D)),
    }
    tree = {"embed": normal((V, D)),
            "blocks": {"layer0": {"norm1": jnp.ones((L, D), dt),
                                  "mixer": mixer}},
            "final_norm": jnp.ones((D,), dt)}
    if not conf["tie_embeddings"]:
        tree["head"] = normal((D, V))
    return tree


def flops_per_token(conf: dict, seq: int) -> float:
    """Training FLOPs per token: 6 x the matmul parameters (in and out
    projections, the head; not the embedding lookup), plus 3 x the
    forward SSD contractions at the published chunk Q = 256 whatever
    chunk the program runs: per head and token, C.B scores and their
    weighting of x over (Q + 1) / 2 positions of the chunk, the chunk
    state's update (B x) and its read-out (C h), 2 FLOPs per
    multiply-add. Elementwise work (conv, gates, norms) is not counted,
    nor is recomputation."""
    D, di, nh, hd, ds, g = _sizes(conf)
    L, V, Q = conf["n_layer"], padded_vocab(conf), SSD_CHUNK
    per_layer = D * (2 * di + 2 * g * ds + nh) + di * D
    matmul = L * per_layer + V * D
    ssd_fwd = nh * ((Q + 1) * (ds + hd) + 4 * hd * ds)
    return 6.0 * matmul + 3.0 * L * ssd_fwd


# ------------------------------------------------------------ reference

def _segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] below the
    diagonal, -inf above it."""
    T = x.shape[-1]
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1),
                   jnp.broadcast_to(x[..., :, None], (*x.shape, T)), 0.0)
    s = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(X, A, B, C, mm, chunk=SSD_CHUNK):
    """Minimal chunked SSD (paper listing). X (b, s, h, p) already scaled
    by dt, A (b, s, h) = dt * A, B and C (b, s, h, n)."""
    b, s, h, p = X.shape
    chunk = min(chunk, s)
    c = s // chunk

    def chunks(t):
        return t.reshape(b, c, chunk, *t.shape[2:])

    X, A, B, C = map(chunks, (X, A, B, C))
    A = jnp.moveaxis(A, -1, 1)                            # b h c l
    A_cum = jnp.cumsum(A, -1)
    Lm = jnp.exp(_segsum(A))                              # b h c l l
    scores = mm("bclhn,bcshn->bhcls", C, B) * Lm
    Y_diag = mm("bhcls,bcshp->bclhp", scores, X)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)              # b h c l
    states = mm("bclhn,bclhp->bchpn", B, X * jnp.moveaxis(decay, 1, -1)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = mm("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    Y_off = mm("bclhn,bchpn->bclhp", C, states) \
        * jnp.moveaxis(jnp.exp(A_cum), 1, -1)[..., None]
    return (Y_diag + Y_off).reshape(b, s, h, p)


def ref_embed(conf, edge, tokens):
    return edge["embed"][tokens] * conf["d_model"] ** 0.5


def ref_layer(conf, p, x, mm):
    """Pre-norm residual Mamba-2 layer in float32."""
    D, di, nh, hd, ds, g = _sizes(conf)
    Bb, S, _ = x.shape
    eps = conf["norm_epsilon"]
    m = p["mixer"]
    h = rms_norm(x, p["norm1"], eps)
    zxbcdt = mm("bsd,de->bse", h, m["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * g * ds]
    dt = zxbcdt[..., 2 * di + 2 * g * ds:]
    K = m["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + S] * m["conv_w"][i] for i in range(K))
                      + m["conv_b"])
    xs = xbc[..., :di].reshape(Bb, S, nh, hd)
    rep = nh // g
    Bm = jnp.repeat(xbc[..., di:di + g * ds].reshape(Bb, S, g, ds), rep, 2)
    Cm = jnp.repeat(xbc[..., di + g * ds:].reshape(Bb, S, g, ds), rep, 2)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    y = ssd(xs * dt[..., None], dt * A, Bm, Cm, mm)
    y = (y + xs * m["D_skip"][:, None]).reshape(Bb, S, di)
    y = rms_norm(y * jax.nn.silu(z), m["norm"], eps)
    return x + mm("bse,ed->bsd", y, m["out_proj"])


def ref_final_norm(conf, edge, x):
    return rms_norm(x, edge["final_norm"], conf["norm_epsilon"])


def head_weight(conf, edge):
    return edge["embed"] if conf["tie_embeddings"] else edge["head"].T
