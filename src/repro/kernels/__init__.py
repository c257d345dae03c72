"""Pallas TPU kernels (flash attention, Mamba-2 SSD scan) with pure-jnp
oracles in ``ref.py``."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret the kernel body unless the default backend is
    a TPU, so a chip always runs compiled Mosaic and the CPU tests keep
    interpret mode. An explicit bool wins (compile rehearsals against a
    described TPU topology pass ``False`` from a CPU process)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
