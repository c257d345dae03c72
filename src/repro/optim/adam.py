"""AdamW (hand-written — optax is not available offline).

State: fp32 first/second moments + step counter. Supports a
``state_dtype`` override (bf16 moments) — one of the memory levers the
roofline hillclimb exercises for the 1T-param Kimi config.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    state_dtype: str = "float32"

    def init(self, params):
        return adamw_init(params, self.state_dtype)

    def update(self, params, state, grads, step, lr=None):
        return adamw_update(self, params, state, grads, step,
                            self.lr if lr is None else lr)


def adamw_init(params, state_dtype="float32"):
    dt = jnp.dtype(state_dtype)

    def zeros(p):           # on p's devices, not the default device
        return jnp.zeros_like(p, dtype=dt)

    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
    }


@jax.named_scope("optimizer")
def adamw_update(opt: AdamW, params, state, grads, step, lr):
    step = jnp.asarray(step, jnp.int32) + 1
    b1, b2 = opt.b1, opt.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    dt = jnp.dtype(opt.state_dtype)

    def upd(p, m, v, g):
        g32 = g.astype(jnp.float32)
        m = (b1 * m.astype(jnp.float32) + (1 - b1) * g32)
        v = (b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32)
        mhat = m / c1
        vhat = v / c2
        delta = mhat / (jnp.sqrt(vhat) + opt.eps)
        if p.ndim >= 2:  # decay matrices only (norms/bias exempt)
            delta = delta + opt.weight_decay * p.astype(jnp.float32)
        newp = p.astype(jnp.float32) - lr * delta
        return newp.astype(p.dtype), m.astype(dt), v.astype(dt)

    out = jax.tree.map(upd, params, state["mu"], state["nu"], grads)
    newp = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    newm = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    newv = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return newp, {"mu": newm, "nu": newv}


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in leaves))


@jax.named_scope("optimizer")
def clip_by_global_norm(grads, max_norm: float):
    n = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), n
