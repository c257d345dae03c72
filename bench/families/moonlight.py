"""Moonlight-16B-A3B family (``model_type`` deepseek_v3): latent attention
(MLA) with no query compression, a leading dense SwiGLU layer, then MoE
layers of sigmoid-routed experts (top k by score + correction bias, the
chosen scores normalised and scaled) beside shared experts; RMSNorm
pre-norm, untied head, no embedding scale.

Same interface as ``bench/families/dense.py``. The configuration file
holds the routed experts this chip computes (``n_routed_experts``) and,
under ``deployment``, over how many chips each layer's experts are
spread and which share is this one's: the router scores all of them,
this chip computes its own. The reference computes each held expert on
every token, weighted by its gate (0 where the token did not choose
it): no sort and no grouped matmul. ``ref_embed`` takes the embedding
and the leading dense layers; ``ref_layer`` one MoE layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.dense import _rope, rms_norm

QUERY_BLOCK = 512        # reference attention: queries per block
TOKEN_BLOCK = 4096       # reference FFNs: tokens per block
HI = jax.lax.Precision.HIGHEST


def _sizes(conf):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"])


def _share(conf):
    """(chips sharing each MoE layer's experts, this chip's index)."""
    d = conf["deployment"]
    return d["expert_shards"], d["expert_shard"]


def _even_share(conf) -> bool:
    """Whether the held experts take their even share of each batch's
    choices, as a trained correction bias keeps them."""
    return bool(conf["deployment"].get("even_share", False))


def _check(conf):
    """The published routing and attention this family writes out."""
    if not (conf["q_lora_rank"] is None and conf["scoring_func"] == "sigmoid"
            and conf["topk_method"] == "noaux_tc" and conf["n_group"] == 1
            and conf["topk_group"] == 1 and conf["norm_topk_prob"]
            and conf["moe_layer_freq"] == 1 and not conf["attention_bias"]
            and not conf["tie_word_embeddings"]):
        raise ValueError(f"{conf['name']}: not the block this family writes")


def program_config(conf: dict, layers: int | None = None):
    from repro.configs.base import ModelConfig
    _check(conf)
    shards, shard = _share(conf)
    if shard:
        raise ValueError(f"{conf['name']}: the one-chip program computes "
                         "the first share of the experts")
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=layers or conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"],
        vocab_size=conf["vocab_size"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_theta=float(conf["rope_theta"]),
        num_experts=conf["n_routed_experts"] * shards,
        experts_per_token=conf["num_experts_per_tok"],
        router_score="sigmoid",
        route_scale=float(conf["routed_scaling_factor"]),
        shared_experts=conf["n_shared_experts"],
        expert_shards=shards, even_share=_even_share(conf),
        # the config gives no coefficient for its sequence-wise balance
        # loss, and the reference's loss is CE alone
        aux_loss_weight=0.0,
        first_dense_layers=conf["first_k_dense_replace"],
        dense_ff=conf["intermediate_size"], embed_scale=False,
        norm_eps=float(conf["rms_norm_eps"]), dtype=conf["dtype"])


def make_params(conf: dict, key):
    """The program's parameter tree: matrices and the embedding N(0,
    0.02), norm gains 1, the correction bias 0; the leading dense layers
    under ``lead``, the MoE layers under ``blocks``, each stacked on a
    leading axis."""
    D, H, r, nope, rope, vd = _sizes(conf)
    F, Fd, V = (conf["moe_intermediate_size"], conf["intermediate_size"],
                conf["vocab_size"])
    Eh, shards = conf["n_routed_experts"], _share(conf)[0]
    Fs = conf["n_shared_experts"] * F
    k = conf["first_k_dense_replace"]
    L = conf["num_hidden_layers"] - k
    dt = jnp.dtype(conf["dtype"])
    ks = iter(jax.random.split(key, 24))

    def normal(shape):
        return (0.02 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    def mla(n):
        return {"wq": normal((n, D, H * (nope + rope))),
                "wkv_a": normal((n, D, r + rope)),
                "kv_norm": jnp.ones((n, r), dt),
                "wkv_b": normal((n, r, H * (nope + vd))),
                "wo": normal((n, H * vd, D))}

    def swiglu(shape_in, shape_out):
        return {"w_gate": normal(shape_in), "w_up": normal(shape_in),
                "w_down": normal(shape_out)}

    lead = {"norm1": jnp.ones((k, D), dt), "norm2": jnp.ones((k, D), dt),
            "mixer": mla(k), "ffn": swiglu((k, D, Fd), (k, Fd, D))}
    ffn = {"router": normal((L, D, Eh * shards)),
           "router_bias": jnp.zeros((L, Eh * shards), dt),
           **swiglu((L, Eh, D, F), (L, Eh, F, D)),
           "shared": swiglu((L, D, Fs), (L, Fs, D))}
    layer = {"norm1": jnp.ones((L, D), dt), "norm2": jnp.ones((L, D), dt),
             "mixer": mla(L), "ffn": ffn}
    return {"embed": normal((V, D)), "head": normal((D, V)),
            "final_norm": jnp.ones((D,), dt),
            "lead": {"layer0": lead}, "blocks": {"layer0": layer}}


def flops_per_token(conf: dict, seq: int) -> float:
    """Training FLOPs per token: 6 x the matmul parameters a token uses
    (the head included, the embedding lookup not; of the held routed
    experts the share of tokens routed to them, K x held / E experts a
    token, as a deployment's chip computes them) plus causal attention:
    scores over the q/k dims and values over the v dims, 2 FLOPs a MAC
    per head and (query, key) pair forward, x3 with the backward, over
    (seq + 1) / 2 keys per query on average. Recomputation is not
    counted."""
    D, H, r, nope, rope, vd = _sizes(conf)
    F, Fd, V = (conf["moe_intermediate_size"], conf["intermediate_size"],
                conf["vocab_size"])
    Eh, shards = conf["n_routed_experts"], _share(conf)[0]
    E, K = Eh * shards, conf["num_experts_per_tok"]
    k = conf["first_k_dense_replace"]
    L = conf["num_hidden_layers"] - k
    attn = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) + H * vd * D
    routed = 3 * D * F * K * Eh / E
    moe = D * E + routed + 3 * D * F * conf["n_shared_experts"]
    matmul = k * (attn + 3 * D * Fd) + L * (attn + moe) + V * D
    attention = (k + L) * 3 * H * (nope + rope + vd) * (seq + 1)
    return 6.0 * matmul + attention


# ------------------------------------------------------------ reference

def _attention(q, k, v, mm):
    """Causal softmax attention, one block of queries at a time.
    q, k (B, S, H, dqk); v (B, S, H, dv) -> (B, S, H * dv)."""
    B, S, H, dqk = q.shape
    qb = min(QUERY_BLOCK, S)
    blocks = q.reshape(B, S // qb, qb, H, dqk).swapaxes(0, 1)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        i, qblk = args
        s = mm("bqhd,bkhd->bhqk", qblk, k) * dqk ** -0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, (jnp.arange(S // qb), blocks))
    return out.swapaxes(0, 1).reshape(B, S, -1)


def _mla(conf, a, h, mm):
    """Latent attention: q per head [q_nope, q_pe]; the latent c_kv and
    one rotary key k_pe from ``wkv_a``; c_kv normed, then each head's
    [k_nope, v] from ``wkv_b``; rotary (rotate-half) on q_pe and k_pe."""
    B, S, _ = h.shape
    D, H, r, nope, rope, vd = _sizes(conf)
    q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, H, nope + rope)
    ckv_pe = mm("bsd,de->bse", h, a["wkv_a"])
    ckv = rms_norm(ckv_pe[..., :r], a["kv_norm"], conf["rms_norm_eps"])
    k_pe = _rope(ckv_pe[..., None, r:], conf["rope_theta"])
    kv = mm("bsr,re->bse", ckv, a["wkv_b"]).reshape(B, S, H, nope + vd)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], conf["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    o = _attention(q, k, kv[..., nope:], mm)
    return mm("bse,ed->bsd", o, a["wo"])


def _swiglu(f, x, mm):
    g = jax.nn.silu(mm("td,df->tf", x, f["w_gate"]))
    return mm("tf,fd->td", g * mm("td,df->tf", x, f["w_up"]), f["w_down"])


def _by_tokens(fn, x, d_out=None):
    """``fn`` over (tokens, width) blocks of x (B, S, width), each
    recomputed in the backward rather than stored; out (B, S, d_out),
    d_out the width unless given."""
    B, S, W = x.shape
    t = x.reshape(-1, W)
    tb = min(TOKEN_BLOCK, t.shape[0])
    out = jax.lax.map(jax.checkpoint(fn), t.reshape(-1, tb, W))
    return out.reshape(B, S, d_out or W)


def _even_offset(b, held, k):
    """The offset on the held experts' biased scores b (T, E) under
    which exactly T k held / E of the top-k choices are theirs, midway
    between the two nearest offsets: token t holds j of its k choices
    while the offset lies between the j-th and (j+1)-th of its entry
    points, the gap from its j-th best held expert to its (k - j + 1)-th
    best other one."""
    T, E = b.shape
    mine = -jnp.sort(-b[:, held], -1)
    rest = -jnp.sort(-b[:, ~held], -1)
    entry = jnp.stack([rest[:, k - j] - mine[:, j - 1]
                       for j in range(1, min(k, int(held.sum())) + 1)], -1)
    n = T * k * int(held.sum()) // E
    e = jnp.sort(entry.reshape(-1))
    below = e[n - 1] if n else e[0] - 1
    above = e[n] if n < e.size else e[-1] + 1
    return (below + above) / 2


def _moe(conf, f, x, mm):
    """Router over all E experts, then this chip's held experts and the
    shared experts, per token: sigmoid scores s; the top K of s + the
    correction bias (with the held experts' offset to their even share
    of the batch, where the deployment says so); the chosen s, over
    their sum, x the routed scaling factor."""
    Eh, (shards, shard) = conf["n_routed_experts"], _share(conf)
    K = conf["num_experts_per_tok"]
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    s = jax.nn.sigmoid(mm("td,de->te", xt, f["router"]))
    b = jax.lax.stop_gradient(s + f["router_bias"])
    if _even_share(conf) and shards > 1:
        held = np.arange(Eh * shards) // Eh == shard
        b = b + jnp.where(held, _even_offset(b, held, K), 0.0)
    _, idx = jax.lax.top_k(b, K)
    chosen = jnp.take_along_axis(s, idx, -1)
    gates = (conf["routed_scaling_factor"] * chosen
             / jnp.sum(chosen, -1, keepdims=True))
    w = jnp.stack([jnp.sum(jnp.where(idx == shard * Eh + e, gates, 0.0), -1)
                   for e in range(Eh)], -1)                       # (T, Eh)

    def tokens(blk):
        xb, wb = blk[:, :D], blk[:, D:]
        y = _swiglu(f["shared"], xb, mm)
        for e in range(Eh):
            expert = {n: f[n][e] for n in ("w_gate", "w_up", "w_down")}
            y = y + wb[:, e:e + 1] * _swiglu(expert, xb, mm)
        return y

    return _by_tokens(tokens, jnp.concatenate([xt, w], -1).reshape(B, S, -1),
                      D)


def _dense_layer(conf, p, x, mm):
    eps = conf["rms_norm_eps"]
    x = x + _mla(conf, p["mixer"], rms_norm(x, p["norm1"], eps), mm)
    return x + _by_tokens(lambda t: _swiglu(p["ffn"], t, mm),
                          rms_norm(x, p["norm2"], eps))


def ref_embed(conf, edge, tokens):
    """The embedding (not scaled), then the leading dense layers."""
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HI)  # noqa: E731
    x = edge["embed"][tokens]
    lead = edge["lead"]["layer0"]
    for i in range(conf["first_k_dense_replace"]):
        x = _dense_layer(conf, jax.tree.map(lambda a, i=i: a[i], lead), x, mm)
    return x


def ref_layer(conf, p, x, mm):
    """One MoE layer in float32; ``mm`` is the contraction (einsum) that
    the reference or its lower-precision control uses."""
    eps = conf["rms_norm_eps"]
    x = x + _mla(conf, p["mixer"], rms_norm(x, p["norm1"], eps), mm)
    return x + _moe(conf, p["ffn"], rms_norm(x, p["norm2"], eps), mm)


def ref_final_norm(conf, edge, x):
    return rms_norm(x, edge["final_norm"], conf["rms_norm_eps"])


def head_weight(conf, edge):
    """(V, D) output projection."""
    return edge["head"].T
