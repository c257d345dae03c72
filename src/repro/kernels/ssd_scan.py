"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Grid: (B*nh, n_chunks) — chunks are the minor (sequential) axis, so the
(hd, ds) f32 state scratch in VMEM carries the inter-chunk recurrence.
Per chunk the kernel computes the intra-chunk quadratic term
(C B^T ⊙ decay) @ (x·dt) on the MXU plus the carried-state contribution,
then updates the state — the SSD algorithm of arXiv:2405.21060 §6 laid
out for VMEM tiles (chunk=128 keeps every operand MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int, nh: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)             # (Q, hd)
    dt_row = dt_ref[0].astype(jnp.float32)       # (1, Q)
    A = a_ref[pl.program_id(0) % nh]             # scalar decay rate (<0)
    Bm = b_ref[0].astype(jnp.float32)            # (Q, ds)
    Cm = c_ref[0].astype(jnp.float32)            # (Q, ds)

    # dt arrives lane-major; the column copies and the inclusive cumsums
    # come from masked lane/sublane reductions of (Q, Q) tiles
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iota_t <= iota_i
    dt_sq = jnp.broadcast_to(dt_row, (chunk, chunk))          # [i, t] = dt_t
    dt = jnp.sum(jnp.where(iota_i == iota_t, dt_sq, 0.0), axis=1,
                 keepdims=True)                  # (Q, 1)
    a_sq = dt_sq * A                             # [i, t] = a_t log-decays
    cum = jnp.sum(jnp.where(lower, a_sq, 0.0), axis=1,
                  keepdims=True)                 # (Q, 1) inclusive
    a_col = jnp.broadcast_to(dt * A, (chunk, chunk))          # [i, t] = a_i
    cum_row = jnp.sum(jnp.where(iota_i <= iota_t, a_col, 0.0), axis=0,
                      keepdims=True)             # (1, Q) inclusive
    # L[i, t] = exp(cum_i - cum_t) for t <= i
    L = jnp.where(lower, jnp.exp(cum - cum_row), 0.0)

    xdt = x * dt                                 # (Q, hd)
    scores = (Cm @ Bm.T) * L                     # (Q, Q)
    y_intra = scores @ xdt                       # (Q, hd)

    h = h_ref[...]                               # (hd, ds)
    y_inter = (Cm @ h.T) * jnp.exp(cum)          # (Q, hd)

    total = jnp.sum(a_sq[:1], axis=1, keepdims=True)      # (1, 1) = cum_Q
    decay_out = jnp.exp(total - cum)             # (Q, 1)
    h_new = h * jnp.exp(total) + (xdt * decay_out).T @ Bm   # (hd, ds)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    h_ref[...] = h_new


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128,
             interpret: bool | None = None):
    """Chunked SSD scan (``interpret=None``: see ``kernels.resolve_interpret``).

    x: (Bb, S, nh, hd); dt: (Bb, S, nh) (already softplus'd);
    A: (nh,) negative decay rates; B, C: (Bb, S, nh, ds) (groups already
    broadcast to heads). Returns y: (Bb, S, nh, hd).
    """
    Bb, S, nh, hd = x.shape
    ds = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    # (B*nh, S, ...) layout, head-major; dt lane-major as (B*nh, 1, S)
    # so its (1, Q) block tiles, A whole in SMEM (indexed per head)
    xf = x.transpose(0, 2, 1, 3).reshape(Bb * nh, S, hd)
    dtf = dt.transpose(0, 2, 1).reshape(Bb * nh, 1, S)
    bf = B.transpose(0, 2, 1, 3).reshape(Bb * nh, S, ds)
    cf = C.transpose(0, 2, 1, 3).reshape(Bb * nh, S, ds)

    kernel = functools.partial(_ssd_kernel, chunk=Q, nh=nh)
    y = pl.pallas_call(
        kernel,
        grid=(Bb * nh, nc),
        in_specs=[
            pl.BlockSpec((1, Q, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, j: (b, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, ds), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Q, ds), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, hd), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb * nh, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xf, dtf, A.astype(jnp.float32), bf, cf)
    return y.reshape(Bb, nh, S, hd).transpose(0, 2, 1, 3)
