"""The plain float32 reference: the cell's first training steps, written
apart from the program under test.

It imports nothing of the program. The weights come from the benchmark's
own ``make_params`` (the same draw from the seed that the program was
given), upcast to float32. Each step is a forward pass layer by layer,
then the backward pass layer by layer through ``jax.vjp`` of the same
layer function (a layer's activations are recomputed from its stored
input), clipping by the global norm, and AdamW. Contractions run at
``HIGHEST`` precision and everything else in float32; the parameters are
kept in the configuration's type between steps, as the configuration
states (as stored arrays, so that no compiler drops the rounding as
excess precision), and the moments in float32.

Layers are spread over the given devices in contiguous runs, so a model
whose float32 state does not fit one chip runs on the cell's four.

``precision="fp8"`` is the control: every operand of every contraction,
forward and backward, is rounded to float8 e4m3 under a per-tensor scale
(its largest magnitude maps to the format's largest finite value).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

CE_CHUNK = 1024          # tokens per block of logits


@dataclass(frozen=True)
class AdamW:
    """The optimizer the cells state: the launcher's defaults."""
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    return _round_fp8(x)


fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def contraction(precision: str):
    """The einsum the reference (``f32``) or the control (``fp8``) uses."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=hi)
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, fp8(a), fp8(b), precision=hi)
    raise ValueError(f"unknown reference precision {precision!r}")


def split_tree(tree):
    """Program layout -> (edge leaves, per-layer dicts)."""
    blocks = tree["blocks"]["layer0"]
    n = jax.tree.leaves(blocks)[0].shape[0]
    edge = {k: v for k, v in tree.items() if k != "blocks"}
    layers = [jax.tree.map(lambda a, i=i: a[i], blocks) for i in range(n)]
    return edge, layers


def tree_norms(prefix: str, tree) -> dict:
    return {prefix + jax.tree_util.keystr(p, simple=True, separator="/"):
            float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(
                jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32)))), tree))}


class Reference:
    """Runs ``steps`` AdamW steps of the family's plain model and keeps
    what the comparison reads: each step's loss, the first clipped
    gradient's norm per leaf, and each leaf's change after the last
    step."""

    def __init__(self, family, conf: dict, devices, *,
                 precision: str = "f32", opt: AdamW = AdamW()):
        self.fam, self.conf, self.opt = family, conf, opt
        self.devices = list(devices)
        mm = contraction(precision)
        fam = family

        def f32(tree):
            return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

        def layer(p, x):
            return fam.ref_layer(conf, p, x, mm)

        def layer_bwd(p, x, dy):
            return jax.vjp(layer, f32(p), x)[1](dy)

        def embed(edge, tokens):
            return fam.ref_embed(conf, edge, tokens)

        def embed_bwd(edge, tokens, dx):
            return jax.vjp(lambda e: embed(e, tokens), f32(edge))[1](dx)[0]

        def head_loss(edge, x, labels):
            h = fam.ref_final_norm(conf, edge, x)
            w = fam.head_weight(conf, edge)
            h = h.reshape(-1, h.shape[-1])
            lab = labels.reshape(-1)
            c = min(CE_CHUNK, lab.shape[0])
            n = lab.shape[0] // c

            @jax.checkpoint
            def chunk(tot, args):
                hc, lc = args
                logits = mm("td,vd->tv", hc, w)
                lse = jax.nn.logsumexp(logits, -1)
                gold = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
                return tot + jnp.sum(lse - gold), None

            tot, _ = jax.lax.scan(
                chunk, jnp.zeros((), jnp.float32),
                (h.reshape(n, c, -1), lab.reshape(n, c)))
            return tot / lab.shape[0]

        def head_grad(edge, x, labels):
            return jax.value_and_grad(head_loss, argnums=(0, 1))(
                f32(edge), x, labels)

        def adam(p, m, v, g, scale, t):
            o = self.opt
            dtype, p = p.dtype, p.astype(jnp.float32)
            g = g * scale
            m = o.b1 * m + (1 - o.b1) * g
            v = o.b2 * v + (1 - o.b2) * g * g
            d = (m / (1 - o.b1 ** t)) / (jnp.sqrt(v / (1 - o.b2 ** t)) + o.eps)
            if p.ndim >= 2:              # matrices decay; norms, biases,
                d = d + o.weight_decay * p   # per-head SSM scalars do not
            return (p - o.lr * d).astype(dtype), m, v

        def adam_tree(p, m, v, g, scale, t):
            out = jax.tree.map(lambda *a: adam(*a, scale, t), p, m, v, g)
            pick = lambda i: jax.tree.map(  # noqa: E731
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            return pick(0), pick(1), pick(2)

        def sq(tree):
            return sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(tree))

        self._layer = jax.jit(lambda p, x: layer(f32(p), x))
        self._layer_bwd = jax.jit(layer_bwd)
        self._embed = jax.jit(lambda e, t: embed(f32(e), t))
        self._embed_bwd = jax.jit(embed_bwd)
        self._head = jax.jit(head_grad)
        self._adam = jax.jit(adam_tree)
        self._sq = jax.jit(sq)
        self._scale = jax.jit(lambda g, s: jax.tree.map(lambda a: a * s, g))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))

    # ------------------------------------------------------------ state
    def _dev(self, i: int):
        return self.devices[i * len(self.devices) // len(self.layers)]

    def load(self, tree):
        """Take the weights (program layout, any device, their own type)."""
        edge, layers = split_tree(tree)
        self.layers = [None] * len(layers)        # sizes _dev()
        self.layers = [jax.device_put(lp, self._dev(i))
                       for i, lp in enumerate(layers)]
        self.edge = jax.device_put(edge, self.devices[0])
        self.edge0, self.layers0 = self.edge, list(self.layers)
        zeros = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jnp.zeros(a.shape, jnp.float32, device=a.device), t)
        self.m = [zeros(self.edge)] + [zeros(lp) for lp in self.layers]
        self.v = [zeros(self.edge)] + [zeros(lp) for lp in self.layers]
        self.t = 0
        self.losses, self.first_grad = [], None

    # ------------------------------------------------------------- step
    def step(self, tokens, labels):
        """One AdamW step on the given rows (host arrays)."""
        dev_last = self._dev(len(self.layers) - 1)
        tok0 = jax.device_put(tokens, self.devices[0])
        x = self._embed(self.edge, tok0)
        acts = []
        for i, lp in enumerate(self.layers):
            x = jax.device_put(x, self._dev(i))
            acts.append(x)
            x = self._layer(lp, x)
        edge_last = jax.device_put(self.edge, dev_last)
        loss, (g_edge_head, dx) = self._head(
            edge_last, x, jax.device_put(labels, dev_last))
        g_layers = [None] * len(self.layers)
        for i in reversed(range(len(self.layers))):
            dx = jax.device_put(dx, self._dev(i))
            g_layers[i], dx = self._layer_bwd(self.layers[i], acts[i], dx)
        del acts
        g_edge = self._embed_bwd(self.edge, tok0,
                                 jax.device_put(dx, self.devices[0]))
        g_edge = jax.tree.map(jnp.add, g_edge,
                              jax.device_put(g_edge_head, self.devices[0]))
        norm = float(np.sqrt(sum(float(self._sq(g))
                                 for g in [g_edge, *g_layers])))
        scale = min(1.0, self.opt.clip_norm / max(norm, 1e-9))
        self.t += 1
        if self.first_grad is None:
            self.first_grad = {
                **tree_norms("", self._scale(g_edge, scale)),
                **{k: v for i, g in enumerate(g_layers)
                   for k, v in tree_norms(f"L{i}/", self._scale(g, scale)).items()}}
        parts = [self.edge, *self.layers]
        grads = [g_edge, *g_layers]
        for j, (p, g) in enumerate(zip(parts, grads, strict=True)):
            parts[j], self.m[j], self.v[j] = self._adam(
                p, self.m[j], self.v[j], g, jnp.float32(scale),
                jnp.float32(self.t))
        self.edge, self.layers = parts[0], parts[1:]
        self.losses.append(float(loss))
        return float(loss)

    def change(self) -> dict:
        """Norm of each leaf's change since ``load``."""
        out = tree_norms("", self._delta(self.edge, self.edge0))
        for i, (a, b) in enumerate(zip(self.layers, self.layers0, strict=True)):
            out.update(tree_norms(f"L{i}/", self._delta(a, b)))
        return out

    def free(self):
        self.edge = self.edge0 = self.layers = self.layers0 = None
        self.m = self.v = None
