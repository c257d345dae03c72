"""Runtime gradient-sync variants for data-parallel dense layers — the
paper's strategy options executed for real in JAX (shard_map over the data
axis + custom_vjp):

  * "allreduce" — dW_local then psum over the data axis (DP-NCCL analogue)
  * "ps"        — reduce-scatter + all-gather (sharded parameter server /
                  ZeRO round-robin owners, the TPU-idiomatic PS)
  * "sfb"       — sufficient factor broadcasting: all-gather the factors
                  (activations x and output grads dy) and recompute
                  dW = x_gathered^T @ dy_gathered locally. Mathematically
                  identical, no gradient tensor on the wire. Wire bytes:
                  2*B*(H1+H2) vs H1*H2 — wins at small per-step batch,
                  exactly the paper's Table 5 regime.

All three produce bit-comparable gradients (tested allclose vs the
single-device reference), demonstrating the paper's losslessness claim on
the real execution engine rather than only in the simulator.
"""
from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

SYNC_MODES = ("allreduce", "ps", "sfb")


# -------------------------------------------------- grad-sync primitives
# Reusable inside any shard_map body (the dense layers below AND the
# pipeline engine's per-stage backward in repro.exec.engine).

def allreduce_grad(g, axis: str):
    """DP-NCCL analogue: one psum, every shard holds the summed grad."""
    return jax.lax.psum(g, axis)


def ps_grad(g, axis: str, n_dev: int):
    """Sharded parameter server (ZeRO round-robin owners): reduce-scatter
    one flat shard per owner, then all-gather. Pads to a multiple of the
    axis size so arbitrary leaf shapes shard evenly."""
    flat = g.reshape(-1)
    pad = (-flat.size) % n_dev
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)])
    shard = jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                 tiled=True)
    full = jax.lax.all_gather(shard, axis, tiled=True)
    if pad:
        full = full[:g.size]
    return full.reshape(g.shape)


def tree_grad_sync(grads, axis: str, sync: str, n_dev: int):
    """Apply one sync mode to every leaf of a gradient pytree. ``sfb``
    is intentionally absent: SFB does not sync gradients — callers
    broadcast the sufficient factors and recompute (see
    ``repro.exec.engine``'s backward and ``sfb_dense_apply`` below)."""
    if n_dev <= 1:
        return grads
    if sync == "allreduce":
        return jax.tree.map(lambda g: allreduce_grad(g, axis), grads)
    if sync == "ps":
        return jax.tree.map(lambda g: ps_grad(g, axis, n_dev), grads)
    raise ValueError(f"tree_grad_sync cannot apply {sync!r} "
                     f"(use one of allreduce|ps)")


def sfb_dense_apply(mesh: Mesh, axis: str, sync: str):
    """Returns dense(x, w) with x batch-sharded over ``axis``, w replicated,
    and the chosen gradient synchronization executed explicitly.

    custom_vjp sits OUTSIDE shard_map so the only collectives in the
    backward pass are the ones the sync mode asks for (shard_map's own
    transpose would otherwise add a redundant psum for the replicated w).
    """
    assert sync in SYNC_MODES, sync

    fwd_sm = shard_map(lambda x, w: x @ w, mesh=mesh,
                       in_specs=(P(axis, None), P(None, None)),
                       out_specs=P(axis, None), check_vma=False)
    dx_sm = shard_map(lambda dy, w: dy @ w.T, mesh=mesh,
                      in_specs=(P(axis, None), P(None, None)),
                      out_specs=P(axis, None), check_vma=False)

    n_dev = mesh.shape[axis]

    def _dw_local(x, dy):
        if sync == "sfb":
            xg = jax.lax.all_gather(x, axis, tiled=True)
            dyg = jax.lax.all_gather(dy, axis, tiled=True)
            return xg.T @ dyg
        if sync == "ps":
            return ps_grad(x.T @ dy, axis, n_dev)
        return allreduce_grad(x.T @ dy, axis)

    # dw is identical on every shard after the sync -> replicated out_spec
    dw_sm = shard_map(_dw_local, mesh=mesh,
                      in_specs=(P(axis, None), P(axis, None)),
                      out_specs=P(None, None), check_vma=False)

    @jax.custom_vjp
    def dense(x, w):
        return fwd_sm(x, w)

    def fwd(x, w):
        return fwd_sm(x, w), (x, w)

    def bwd(res, dy):
        x, w = res
        return dx_sm(dy, w), dw_sm(x, dy)

    dense.defvjp(fwd, bwd)
    return dense


def dp_mlp_loss(mesh: Mesh, axis: str, sync: str, widths):
    """A small data-parallel MLP whose every layer syncs gradients via the
    chosen mode (used by tests + the SFB example/benchmark)."""
    dense = sfb_dense_apply(mesh, axis, sync)

    def loss_fn(params, x, y):
        h = x
        for i, w in enumerate(params):
            h = dense(h, w)
            if i < len(params) - 1:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)
    return loss_fn


def sfb_wire_bytes(batch: int, h1: int, h2: int, d: int,
                   itemsize: int = 4) -> dict:
    """Napkin model of per-step wire bytes (ring collectives)."""
    return {
        "allreduce": 2 * (d - 1) / d * h1 * h2 * itemsize,
        "ps": 2 * (d - 1) / d * h1 * h2 * itemsize,
        "sfb": (d - 1) / d * batch * (h1 + h2) * itemsize * 2,
    }
