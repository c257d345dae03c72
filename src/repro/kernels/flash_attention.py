"""Flash attention Pallas TPU kernel (online softmax, causal + optional
sliding window).

Grid: (B*H, nQ, nK) — the K dimension is the minor (sequential) grid axis,
so the f32 accumulator / running max / denominator scratch in VMEM carries
across K blocks for a fixed Q block. BlockSpecs tile Q/K/V as
(block, head_dim) VMEM tiles; block sizes default to 128 (MXU-aligned).
The TPU memory hierarchy shapes the design: K/V stream HBM->VMEM block by
block, the (bq, bk) score tile lives entirely in VMEM/VREGs, and only the
(bq, hd) output tile is written back.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    s = (q @ k.T) * scale                        # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_ref[...] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q/k/v: (B, H, S, hd) -> (B, H, S, hd). MHA-level (GQA expansion in
    ops.py). ``interpret=None`` compiles Mosaic on a TPU backend and
    interprets the kernel body anywhere else (see ``kernels.resolve_interpret``)."""
    B, H, S, hd = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window)
    call = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denominator
        ],
        interpret=resolve_interpret(interpret),
    )
    return _forward_only(call)(qf, kf, vf).reshape(B, H, S, hd)


def _forward_only(call):
    """The kernel has no backward pass: differentiating it raises a plain
    error instead of failing deep inside Pallas' own autodiff."""
    @jax.custom_vjp
    def f(*xs):
        return call(*xs)

    def bwd(_res, _g):
        raise NotImplementedError(
            "the Pallas flash_attention kernel has no backward pass; "
            "train through kernels.ops.gqa_splash_attention")

    f.defvjp(lambda *xs: (call(*xs), None), bwd)
    return f
