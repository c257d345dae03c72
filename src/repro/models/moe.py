"""Top-k MoE: the dropless expert layer (``dropless_fwd``, where
``cfg.dropless``) and the capacity dispatch (``moe_fwd``).

Dropless: the router scores every expert; this chip keeps the
assignments to the experts it holds (one share of ``cfg.expert_shards``;
on one chip the first), sorts them by expert, and runs each SwiGLU matrix
as one grouped matmul over the sorted rows (``kernels.ops.grouped_matmul``),
so every assignment is computed whatever the imbalance. Outputs, weighted
by their gates, go back to their tokens; shared experts run on every
token. What the experts held elsewhere add is not computed here: on one
chip the layer runs without the exchange. Named scopes inside ``moe``:
``router``, ``dispatch``, ``experts`` (the grouped contractions),
``combine``, ``shared_expert``.

Capacity dispatch: top-k MoE with grouped, gather/scatter-based slots.

Tokens are reshaped to (G, T/G, D) where G tracks the data-parallel shard
count; routing + capacity ranking happen per group (local under SPMD), and
the (g, e, c, d) -> (e, g, c, d) transpose before the expert matmuls is the
canonical GSPMD all-to-all. No (T, E, C) one-hot tensor is ever
materialized — dispatch/combine are integer gathers/scatters, so HLO FLOPs
stay close to the active-expert compute (keeps the roofline "useful ratio"
honest at kimi-k2 scale).

Includes the Switch-style auxiliary load-balance loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ops import grouped_matmul
from repro.models.layers import ParamDef, mlp_defs, mlp_fwd
from repro.parallel.sharding import current_rules, logical_shard

TOKEN_BLOCK = 8192       # tokens the dropless layer sorts at a time

# how a stack's MoE statistics combine over layers
STAT_MERGE = {"aux": jnp.add, "moe_rows_here": jnp.add,
              "moe_load_max": jnp.maximum}


def zero_stats(cfg) -> dict:
    """MoE statistics before any layer: the aux loss, plus the dropless
    layer's counters where ``cfg`` (None: no MoE layer) has that layer."""
    out = {"aux": jnp.zeros((), jnp.float32)}
    if cfg is not None and cfg.dropless:
        out["moe_rows_here"] = jnp.zeros((), jnp.int32)
        out["moe_load_max"] = jnp.zeros((), jnp.float32)
    return out


def moe_defs(cfg) -> dict:
    """Router over all ``num_experts``; SwiGLU weights of the experts held
    here (all of them unless the config gives this chip a share)."""
    D, F, E, Eh = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    defs = {
        "router": ParamDef((D, E), ("embed", "experts")),
        "w_gate": ParamDef((Eh, D, F), ("experts", "expert_embed", "mlp")),
        "w_up": ParamDef((Eh, D, F), ("experts", "expert_embed", "mlp")),
        "w_down": ParamDef((Eh, F, D), ("experts", "mlp", "expert_embed")),
    }
    if cfg.router_score == "sigmoid":
        # chooses experts and takes no gradient (DeepSeek-V3 updates it by
        # a rule outside the gradient, which this trainer does not run)
        defs["router_bias"] = ParamDef((E,), (None,), init="zeros")
    if cfg.shared_experts:
        defs["shared"] = mlp_defs(D, cfg.shared_experts * F)
    return defs


def even_share_offset(biased, lo: int, held: int, k: int):
    """The offset that, added to experts ``[lo, lo + held)`` of the
    biased scores (T, E), gives them an even share, T k held / E, of the
    top-k choices. Token t's j-th best held expert enters its top k once
    the offset passes d[t, j] = (its (k - j + 1)-th best other expert) -
    (its j-th best held one), which grows with j; so N = T k held / E
    choices fall here for any offset between the N-th and the (N+1)-th
    smallest d, and the midpoint is taken."""
    T, E = biased.shape
    if E - held < k:
        raise ValueError("an even share needs k experts held elsewhere")
    j = min(k, held)
    mine = jax.lax.top_k(biased[:, lo:lo + held], j)[0]
    rest = jnp.concatenate([biased[:, :lo], biased[:, lo + held:]], 1)
    rest = jax.lax.top_k(rest, k)[0][:, k - j:][:, ::-1]
    d = jnp.sort((rest - mine).reshape(-1))
    d = jnp.concatenate([d[:1] - 1, d, d[-1:] + 1])
    n = T * k * held // E
    return (d[n] + d[n + 1]) / 2


def route(cfg, p, x, shard: int = 0):
    """Router over all experts. x (T, D) -> (expert ids (T, K), gates
    (T, K) f32, aux loss). Scores in float32: softmax, or sigmoid with
    the top k chosen by score + correction bias; the chosen scores are
    normalised to sum to ``cfg.route_scale``. Where ``cfg.even_share``,
    the held experts (share ``shard``) choose with their bias offset by
    ``even_share_offset`` on this batch."""
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = jax.lax.stop_gradient(
            scores + p["router_bias"].astype(jnp.float32))
        if cfg.even_share and cfg.expert_shards > 1:
            Eh = cfg.held_experts
            lo = shard * Eh
            biased = biased.at[:, lo:lo + Eh].add(
                even_share_offset(biased, lo, Eh, K))
        _, e_idx = jax.lax.top_k(biased, K)
        gates = jnp.take_along_axis(scores, e_idx, axis=-1)
        probs = scores / jnp.sum(scores, -1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, e_idx = jax.lax.top_k(probs, K)
    gates = cfg.route_scale * gates / jnp.maximum(
        gates.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(e_idx[:, 0], E, dtype=jnp.float32), 0)
    aux = E * jnp.sum(density * jnp.mean(probs, 0))
    return e_idx, gates, aux


# Dispatch and combine move rows by a permutation of the T x K
# assignments and its inverse. Each one's cotangent goes back by a gather
# with the other, not by a scatter-add.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k: int):
    """Row a of the result is ``x[order[a] // k]``: x (T, D) -> (T k, D)."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    gt = g[inv].reshape(-1, k, g.shape[-1])
    return jnp.sum(gt.astype(jnp.float32), 1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _held_block(cfg, p, shard: int, blk):
    """One token block through the experts of share ``shard``: xt (Tb,
    D), e_idx and gates (Tb, K) -> (y (Tb, D), rows per held expert
    (Eh,))."""
    xt, e_idx, gates = blk
    Tb, D = xt.shape
    K, Eh = cfg.experts_per_token, cfg.held_experts
    with jax.named_scope("dispatch"):
        local = e_idx.reshape(-1) - shard * Eh                  # (Tb*K,)
        held = (local >= 0) & (local < Eh)
        group = jnp.where(held, local, Eh)     # elsewhere: a last group
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(Tb * K, dtype=jnp.int32))
        sizes = jnp.sum(jax.nn.one_hot(group, Eh + 1, dtype=jnp.int32), 0)
        xs = _dispatch(xt, order, inv, K)      # rows by expert
    with jax.named_scope("experts"):
        # the last group's rows (held elsewhere) are not computed and
        # come out as zeros
        g = grouped_matmul(xs, p["w_gate"], sizes)
        u = grouped_matmul(xs, p["w_up"], sizes)
    h = jax.nn.silu(g) * u
    with jax.named_scope("experts"):
        ys = grouped_matmul(h, p["w_down"], sizes)
    with jax.named_scope("combine"):
        w = jnp.where(held, gates.reshape(-1), 0.0).astype(ys.dtype)
        yk = _permute(ys, inv, order) * w[:, None]
        y = jnp.sum(yk.reshape(Tb, K, D).astype(jnp.float32), 1)
    return y.astype(xt.dtype), sizes[:Eh]


def dropless_fwd(cfg, p, x, shard: int = 0):
    """x: (B, S, D) -> (y, MoE statistics: ``aux``, ``moe_rows_here``
    (assignments computed here), ``moe_load_max`` (the fullest held
    expert's rows over the held experts' mean)). ``p`` holds the experts
    ``[shard * held_experts, (shard + 1) * held_experts)``; in an
    expert-parallel run the index is the chip's place on the expert
    axis.

    The held experts take the tokens ``TOKEN_BLOCK`` at a time, each
    block sorted and computed on its own and recomputed in the backward,
    so the sorted rows (a block's tokens x K, since every assignment may
    fall here) are held for one block at a time."""
    B, S, D = x.shape
    T, K, Eh = B * S, cfg.experts_per_token, cfg.held_experts
    xt = x.reshape(T, D)
    with jax.named_scope("router"):
        e_idx, gates, aux = route(cfg, p, xt, shard)
    tb = TOKEN_BLOCK if T % TOKEN_BLOCK == 0 else T
    blocks = (xt.reshape(-1, tb, D), e_idx.reshape(-1, tb, K),
              gates.reshape(-1, tb, K))
    y, sizes = jax.lax.map(
        jax.checkpoint(functools.partial(_held_block, cfg, p, shard)),
        blocks)
    y = y.reshape(T, D)
    if cfg.shared_experts:
        with jax.named_scope("shared_expert"):
            y = y + mlp_fwd(p["shared"], x).reshape(T, D)
    sizes = jnp.sum(sizes, 0)
    rows = jnp.sum(sizes)
    load = jnp.max(sizes) * Eh / jnp.maximum(rows, 1)
    stats = {"aux": aux, "moe_rows_here": rows,
             "moe_load_max": load.astype(jnp.float32)}
    return y.reshape(B, S, D), stats


def capacity(cfg, tokens_per_group: int) -> int:
    c = int(cfg.capacity_factor * cfg.experts_per_token * tokens_per_group
            / cfg.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def num_groups(total_tokens: int) -> int:
    """Dispatch group count = data-parallel shard count when divisible."""
    r = current_rules()
    if r is None or r.mesh is None:
        return 1
    ax = r.mesh_axes("batch")
    if ax is None:
        return 1
    g = r.axis_size(ax)
    return g if total_tokens % g == 0 else 1


def _rank_within_expert(e_idx, E: int):
    """Capacity rank per (token, k) assignment inside one group.

    e_idx: (T, K) int32. Returns pos: (T, K) — the k-major arrival rank of
    each assignment at its expert. Memory: one (T, E) int32 temp per k-slot.
    """
    T, K = e_idx.shape

    def body(base, ek):
        oh = jax.nn.one_hot(ek, E, dtype=jnp.int32)           # (T, E)
        excl = jnp.cumsum(oh, axis=0) - oh                     # exclusive
        pos_k = jnp.take_along_axis(excl + base[None], ek[:, None], axis=1)[:, 0]
        return base + oh.sum(0), pos_k

    base0 = jnp.zeros((E,), jnp.int32)
    _, pos = jax.lax.scan(body, base0, e_idx.T)                # (K, T)
    return pos.T


def moe_fwd(cfg, p, x):
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    G = num_groups(T)
    Tg = T // G
    C = capacity(cfg, Tg)
    xt = x.reshape(G, Tg, D)
    xt = logical_shard(xt, "batch", None, "embed")

    logits = (xt @ p["router"]).astype(jnp.float32)            # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, e_idx = jax.lax.top_k(probs, K)                 # (G, Tg, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    density = jnp.mean(
        jax.nn.one_hot(e_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(density * jnp.mean(probs, axis=(0, 1)))

    pos = jax.vmap(_rank_within_expert, in_axes=(0, None))(e_idx, E)  # (G,Tg,K)
    keep = pos < C
    gate_vals = jnp.where(keep, gate_vals, 0.0)
    safe_pos = jnp.where(keep, pos, C)                         # C drops on scatter

    token_id = jnp.broadcast_to(jnp.arange(Tg)[:, None], (Tg, K))

    def build_idx(eidx_g, pos_g):
        idx = jnp.zeros((E, C), jnp.int32)
        filled = jnp.zeros((E, C), x.dtype)
        idx = idx.at[eidx_g, pos_g].set(token_id, mode="drop")
        filled = filled.at[eidx_g, pos_g].set(1.0, mode="drop")
        return idx, filled

    idx, filled = jax.vmap(build_idx)(e_idx, safe_pos)         # (G, E, C)

    # dispatch: gather token embeddings into expert slots
    xe = jnp.take_along_axis(
        xt[:, :, None, :],                                     # (G, Tg, 1, D)
        idx.reshape(G, E * C)[:, :, None, None], axis=1, mode="clip"
    ).reshape(G, E, C, D) * filled[..., None]
    xe = jnp.swapaxes(xe, 0, 1)                                # (E, G, C, D) — a2a
    xe = logical_shard(xe, "experts", "batch", None, "expert_embed")

    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, p["w_gate"])) \
        * jnp.einsum("egcd,edf->egcf", xe, p["w_up"])
    h = logical_shard(h, "experts", "batch", None, "mlp")
    ye = jnp.einsum("egcf,efd->egcd", h, p["w_down"])          # (E, G, C, D)
    ye = jnp.swapaxes(ye, 0, 1)                                # (G, E, C, D) — a2a
    ye = logical_shard(ye, "batch", "experts", None, "expert_embed")

    if cfg.moe_combine == "scatter":
        # combine on the EXPERT side: weight each slot's output by its
        # token's gate and scatter-add into (G, Tg, D). Under SPMD the
        # expert axis stays local and only the (Tg, D) partial sums cross
        # the mesh (an all-reduce) — instead of all-gathering the full
        # (E, C, D) expert outputs for the token-side gather (§Perf o5).
        gate_slot = jnp.zeros((G, E, C), jnp.float32)
        gate_slot = jax.vmap(
            lambda gs, ei, sp, gv: gs.at[ei, sp].add(gv, mode="drop"))(
            gate_slot, e_idx, safe_pos, gate_vals)
        weighted = ye * gate_slot[..., None].astype(x.dtype)   # (G,E,C,D)

        def scat(idx_g, w_g):
            return jnp.zeros((Tg, D), x.dtype).at[
                idx_g.reshape(E * C)].add(w_g.reshape(E * C, D))
        y = jax.vmap(scat)(idx, weighted)                      # (G, Tg, D)
    else:
        # combine: gather each assignment's expert output, weight, sum
        # over K. Dropped assignments have slot == E*C (out of bounds):
        # clip-gather junk, their gate weight is already zeroed.
        flat_slot = e_idx * C + safe_pos                       # (G, Tg, K)
        yk = jnp.take_along_axis(
            ye.reshape(G, E * C, 1, D),
            flat_slot.reshape(G, Tg * K)[:, :, None, None], axis=1,
            mode="clip").reshape(G, Tg, K, D)
        y = jnp.sum(yk * gate_vals[..., None].astype(x.dtype), axis=2)
    y = logical_shard(y, "batch", None, "embed")
    return y.reshape(B, S, D), aux
