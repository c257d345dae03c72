"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the model layers route through (GQA head
layout, D-skip/gating composition). ``interpret=None`` lets the kernels
decide from the backend: compiled Mosaic on a TPU, the kernel body
interpreted elsewhere (CPU tests).

``gqa_splash_attention`` is the attention the model trains through: the
splash attention kernels that ship with JAX (forward, dq and dkv), with a
block-sparse causal mask so key blocks above the diagonal are never
loaded. ``grouped_matmul`` is the dropless expert layer's contraction:
JAX's megablox grouped matmul (``gmm``, with ``tgmm`` for the weights'
gradient). ``gqa_flash_attention`` wraps this repo's own forward-only
kernel.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

# Splash block sizes by query/key head dim and sequence length: the first
# row of the head dim whose ``min_seq`` the sequence reaches and whose
# blocks all divide it. ``q`` / ``kv`` are the forward's blocks and
# ``kv_compute`` its inner step over a kv block; ``dkv`` the (q, kv)
# blocks of the backward, which computes dq in the same kernel as dk/dv.
# Tuned on one TPU v5e. Head dim 128 (12 q / 2 kv heads, batch 2 at S
# 4096 and batch 1 at S 2048): forward and backward 4.40 ms at S 4096
# against 42.3 ms for the jnp scan, 1.45 ms against 3.91 ms at S 2048; a
# separate dq kernel read 4.9-5.5 ms and 1.5-1.7 ms. Head dim 192 (latent
# attention: 16 heads, v dim 128, batch 4 at S 8192): see PERF.md.
SPLASH_BLOCKS = (
    # head_dim, min_seq, q, kv, kv_compute, dkv
    (128, 4096, 512, 1024, 512, (1024, 1024)),
    (128, 2048, 512, 1024, 512, (512, 512)),
    (192, 8192, 512, 1024, 512, (1024, 1024)),
)
# Below the table, or where no row divides S, only a forced "pallas"
# runs the kernel, with these untuned blocks (the interpreted CPU tests).
FORCED_BLOCKS = (128, 128, 128, (128, 128))


def _blocks(bq, bkv, bkv_c, dkv):
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv_c,
        block_q_dkv=dkv[0], block_kv_dkv=dkv[1],
        block_kv_dkv_compute=dkv[1], use_fused_bwd_kernel=True)


def _tiles(seq, bq, bkv, bkv_c, dkv):
    return all(seq % b == 0 for b in (bq, bkv, *dkv))


def splash_block_sizes(seq: int, head_dim: int) -> "splash.BlockSizes | None":
    """The kernel's tuned block sizes for a sequence of ``seq`` tokens at
    a query/key head dim of ``head_dim``; None where no row of
    ``SPLASH_BLOCKS`` tiles it."""
    for dim, min_seq, *row in SPLASH_BLOCKS:
        if dim == head_dim and seq >= min_seq and _tiles(seq, *row):
            return _blocks(*row)
    return None


def resolve_attn_impl(impl: str, seq: int, window: int, head_dim: int) -> str:
    """``"auto"`` -> ``"pallas"`` (``gqa_splash_attention``) where the
    default backend is a TPU, the attention is full causal (``window``
    0) and a tuned row of ``SPLASH_BLOCKS`` tiles ``seq`` at the query/key
    ``head_dim``; ``"jnp"`` (the query-chunk scan) everywhere else.
    ``"pallas"`` and ``"jnp"`` are returned as they are."""
    if impl != "auto":
        return impl
    if (jax.default_backend() == "tpu" and not window
            and splash_block_sizes(seq, head_dim) is not None):
        return "pallas"
    return "jnp"


class _PallasWithoutKernelMetadata:
    """``jax.experimental.pallas`` as the splash kernels call it, except
    that ``pallas_call`` drops their ``metadata`` (block sizes, for the
    profiler's display only). The compiler prints that metadata as
    indented JSON inside the kernel's instruction, so the instruction ran
    over three lines, with its ``op_name`` (and the ``attention`` scope
    in it) on the last one; without it the instruction is one line, like
    every other op of the compiled text."""

    def __getattr__(self, name):
        return getattr(pl, name)

    @staticmethod
    def pallas_call(*args, metadata=None, **kwargs):
        del metadata
        return pl.pallas_call(*args, **kwargs)


splash.pl = _PallasWithoutKernelMetadata()


@functools.lru_cache(maxsize=16)
def _splash_kernel(seq: int, heads: int, window: int,
                   blocks: "splash.BlockSizes", interpret: bool):
    """One kernel per (shape, mask, blocks): the mask's host-side block
    analysis runs once per process, not on every trace. Built outside any
    trace so the mask tables it holds are concrete arrays."""
    if window:
        # keys in (q - window, q], as the jnp path masks them
        mask = splash_mask.LocalMask((seq, seq), (window - 1, 0), offset=0)
    else:
        mask = splash_mask.CausalMask((seq, seq))
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash_mask.MultiHeadMask([mask] * heads), block_sizes=blocks,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def gqa_splash_attention(q, k, v, *, window: int = 0,
                         interpret: bool | None = None):
    """Causal (optionally sliding-window) GQA attention with a backward.

    q: (B, S, H, hd); k: (B, S, KV, hd); v: (B, S, KV, vd) -> (B, S, H,
    vd). Query head ``h`` attends with kv head ``h // (H // KV)``, as the
    jnp path groups them; the kv heads are not repeated in HBM. Operands stay in their
    dtype on the MXU with f32 accumulation and f32 softmax statistics;
    the score tile never leaves VMEM. Blocks from ``SPLASH_BLOCKS``, else
    ``FORCED_BLOCKS``; raises ValueError where neither tiles S."""
    B, S, H, hd = q.shape
    blocks = splash_block_sizes(S, hd)
    if blocks is None and _tiles(S, *FORCED_BLOCKS):
        blocks = _blocks(*FORCED_BLOCKS)
    if blocks is None:
        raise ValueError(f"no splash block size tiles a sequence of {S}")
    kernel = _splash_kernel(S, H, window, blocks, resolve_interpret(interpret))
    # the kernel applies no softmax scale of its own
    qh = (q * hd ** -0.5).transpose(0, 2, 1, 3)
    o = jax.vmap(kernel)(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return o.transpose(0, 2, 1, 3)


# Grouped matmul tiles: rows (the largest of these that divides the rows),
# contraction and output columns, capped at the operand's size. Tuned on
# one TPU v5e at the Moonlight expert shape (8 experts of 2048 x 1408,
# 49152 sorted rows of which ~6.1k routed here): the SwiGLU's three
# matmuls forward and backward in 5.85 ms at 512 x 512 x 512 against 32.5
# ms at 128 x 128 x 128 and 8.84 ms for ``lax.ragged_dot`` (PERF.md).
GMM_ROW_TILES = (512, 256, 128, 64, 32, 16, 8)
GMM_TILE = 512


def grouped_matmul(x, w, group_sizes, *, interpret: bool | None = None):
    """x (M, K) with its rows in groups, in order: ``group_sizes`` (G + 1,)
    int32 summing to M; w (G, K, N) -> (M, N), rows of group g < G times
    ``w[g]``. The last group's rows take no weight: they come out as
    zeros and get zero gradient, and their tiles are not computed, so
    whatever those rows of x hold (even NaN) reaches nothing."""
    M, K = x.shape
    tm = next((t for t in GMM_ROW_TILES if M % t == 0), M)
    tiling = (tm, min(GMM_TILE, K), min(GMM_TILE, w.shape[-1]))
    return megablox.gmm(x, w, group_sizes, x.dtype, tiling, None, None,
                        False, resolve_interpret(interpret))


def gqa_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd).
    Expands GQA KV heads and routes through this repo's forward-only
    flash kernel (no backward: train through ``gqa_splash_attention``)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.transpose(0, 2, 1, 3)                       # (B, H, S, hd)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    o = flash_attention(qh, kh, vh, causal=causal, window=window,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def mamba_ssd(x, dt, A, B, C, D_skip=None, *, chunk: int = 128,
              interpret: bool | None = None):
    """SSD scan + optional D-skip. Shapes as in kernels.ssd_scan."""
    y = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    if D_skip is not None:
        y = y + x * D_skip[None, None, :, None]
    return y
