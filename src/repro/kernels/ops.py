"""Jit'd public wrappers around the Pallas kernels.

These are the drop-in entry points the model layers can route through
(GQA head expansion, D-skip/gating composition). ``interpret=None`` lets
the kernels decide from the backend: compiled Mosaic on a TPU, the kernel
body interpreted elsewhere (CPU tests).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def gqa_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd).
    Expands GQA KV heads and routes through the flash kernel."""
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
