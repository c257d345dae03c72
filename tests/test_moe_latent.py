"""Moonlight's mechanisms in the program at CPU sizes: the dropless
expert layer's routing and counters, latent attention (MLA) through the
kernel dispatch against the jnp scan, decode through the latent cache,
and the leading dense layer on the pipeline path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch import steps as steps_mod
from repro.models import decode_step, forward, init_cache, init_params
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.layers import init_tree
from repro.models.model import _head
from repro.optim.adam import AdamW
from repro.parallel.sharding import AxisRules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _reduced(**kw):
    return get_reduced("moonlight-16b-a3b").replace(dtype="float32", **kw)


@pytest.mark.parametrize("score,scale", [("sigmoid", 2.446), ("softmax", 1.0)])
def test_gates_sum_to_route_scale(score, scale):
    cfg = _reduced(router_score=score, route_scale=scale)
    p = init_tree(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    e_idx, gates, _ = moe_mod.route(cfg, p, x)
    assert e_idx.shape == gates.shape == (64, cfg.experts_per_token)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), scale, rtol=1e-6)
    # K distinct experts a token
    assert all(len(set(r)) == cfg.experts_per_token for r in e_idx.tolist())


def _plant(params, biases):
    """Router biases per MoE layer that decide every token's experts."""
    ffn = params["blocks"]["layer0"]["ffn"]
    ffn["router_bias"] = jnp.asarray(biases, ffn["router_bias"].dtype)
    return params


@pytest.mark.parametrize("biases,rows,load", [
    # this chip holds experts {0, 1}; every token picks {0, 2} in layer
    # 1, and {0, 1} or {2, 3} in layer 2
    ([[10.0, 0.0, 10.0, 0.0], [10.0, 10.0, 0.0, 0.0]], 1 + 2, 2.0),
    ([[10.0, 0.0, 10.0, 0.0], [0.0, 0.0, 10.0, 10.0]], 1 + 0, 2.0),
])
def test_counters_read_a_planted_routing(biases, rows, load):
    """``moe_rows_here`` sums the assignments computed here over layers;
    ``moe_load_max`` is the fullest held expert over the held mean, the
    largest over layers. In units of tokens T."""
    cfg = _reduced(num_layers=3)
    params = _plant(init_params(cfg, jax.random.PRNGKey(0)), biases)
    B, S = 2, 16
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    opt = AdamW(lr=1e-3)
    step = jax.jit(steps_mod.make_train_step(cfg, opt, AxisRules()))
    _, _, m = step(params, opt.init(params), jnp.asarray(0, jnp.int32), batch)
    assert int(m["moe_rows_here"]) == rows * B * S
    # second case, layer 2: no row here, so nothing is fuller than the mean
    assert float(m["moe_load_max"]) == load


@pytest.mark.parametrize("biases", [
    [[10.0, 10.0, 0.0, 0.0], [10.0, 10.0, 0.0, 0.0]],   # all here
    [[0.0, 0.0, 10.0, 10.0], [10.0, 0.0, 10.0, 0.0]],   # none, then half
])
def test_even_share_holds_this_chip_to_its_share(biases):
    """With ``even_share``, whatever the biases plant, each MoE layer
    computes T K held / E assignments here: T for 2 of 4 experts, top-2."""
    cfg = _reduced(num_layers=3, even_share=True)
    params = _plant(init_params(cfg, jax.random.PRNGKey(0)), biases)
    B, S = 2, 16
    batch = {"tokens": jnp.arange(B * S, dtype=jnp.int32).reshape(B, S),
             "labels": jnp.zeros((B, S), jnp.int32)}
    opt = AdamW(lr=1e-3)
    step = jax.jit(steps_mod.make_train_step(cfg, opt, AxisRules()))
    _, _, m = step(params, opt.init(params), jnp.asarray(0, jnp.int32), batch)
    assert int(m["moe_rows_here"]) == 2 * B * S


def test_grouped_matmul_leaves_rows_held_elsewhere_out():
    """Rows of the last group (assignments held elsewhere) come out as
    zeros and take zero gradient, whatever they hold: here NaN. The
    other groups' rows and the weights' gradients are each group's own
    plain matmul."""
    from repro.kernels.ops import grouped_matmul
    M, K, N = 48, 32, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K)).at[16:].set(jnp.nan)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, K, N))
    sizes = jnp.asarray([5, 0, 11, M - 16], jnp.int32)

    def plain(x, w):
        return jnp.concatenate([x[:5] @ w[0], x[5:16] @ w[2]])

    y = grouped_matmul(x, w, sizes)
    assert not np.any(np.asarray(y[16:]))
    np.testing.assert_allclose(np.asarray(y[:16]), np.asarray(plain(x, w)),
                               rtol=1e-5, atol=1e-5)
    gx, gw = jax.grad(lambda x, w: jnp.sum(jnp.sin(
        grouped_matmul(x, w, sizes))), argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: jnp.sum(jnp.sin(plain(x, w))),
                      argnums=(0, 1))(x[:16], w)
    assert not np.any(np.asarray(gx[16:]))
    np.testing.assert_allclose(np.asarray(gx[:16]), np.asarray(rx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-5, atol=1e-5)


def test_no_row_dropped_and_blocks_agree():
    """Every assignment to a held expert is computed, with every token on
    the same two experts, whether the tokens are sorted all at once or a
    block at a time."""
    cfg = _reduced()
    p = init_tree(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0), jnp.float32)
    p["router_bias"] = jnp.asarray([10.0, 10.0, 0.0, 0.0])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    y_all, stats = moe_mod.dropless_fwd(cfg, p, x)
    assert int(stats["moe_rows_here"]) == 4 * 32 * cfg.experts_per_token
    saved = moe_mod.TOKEN_BLOCK
    moe_mod.TOKEN_BLOCK = 32
    try:
        y_blk, stats_blk = moe_mod.dropless_fwd(cfg, p, x)
    finally:
        moe_mod.TOKEN_BLOCK = saved
    np.testing.assert_allclose(np.asarray(y_blk), np.asarray(y_all),
                               rtol=1e-5, atol=1e-6)
    assert int(stats_blk["moe_rows_here"]) == int(stats["moe_rows_here"])


def test_mla_kernel_matches_jnp_scan():
    """Latent attention through the splash kernel (interpreted here) and
    through the jnp scan: the same output and gradients."""
    cfg = _reduced()
    p = init_tree(attn_mod.attn_defs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    pos = jnp.arange(128)

    def run(impl):
        def loss(p, x):
            y, _ = attn_mod.attention(cfg.replace(attn_impl=impl), p, x, pos)
            return jnp.sum(jnp.sin(y)), y
        (_, y), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
        return y, g
    y_k, g_k = run("pallas")
    y_j, g_j = run("jnp")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_j), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_mla_decode_matches_full_forward():
    """Decode through the cache of normed latents and rotary keys gives
    the full forward's logits at every position."""
    cfg = _reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    h, _, _ = forward(cfg, params, {"tokens": toks}, remat=False)
    full = _head(cfg, params, h)
    cache = init_cache(cfg, B, S + 1)
    assert set(cache) == {"lead", "blocks"}
    assert set(cache["blocks"]["layer0"]) == {"ckv", "k_pe"}
    outs = []
    for t in range(S):
        logits, cache = decode_step(cfg, params, cache, toks[:, t:t + 1], t)
        outs.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), atol=2e-4)


def test_pipeline_stage0_takes_the_leading_layer():
    """Two pipeline stages (stage 0: embedding and the leading dense
    layer; stage 1: the MoE layer, norm, head) give the single-mesh
    step's loss and gradients."""
    code = """
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import init_params, loss_fn
        from repro.exec import PipelineRunner, split_model
        from repro.exec.stages import StagePlan, StageSpec

        cfg = get_reduced("moonlight-16b-a3b").replace(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        f = lambda p, b: loss_fn(cfg, p, b, remat=False)[0]
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(f))(params, batch)
        sp, fns, keys, tied = split_model(cfg, params, 2)
        assert "lead" in sp[0] and "lead" not in sp[1]
        assert jax.tree.leaves(sp[0]["blocks"])[0].shape[0] == 0
        plan = StagePlan(
            stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                              grad_bytes=0, out_bytes=1e5, n_devices=1,
                              gpu_type="cpu") for i in range(2)],
            placement=(0, 1), n_micro=2)
        devs = jax.devices()
        runner = PipelineRunner(fns, plan, [[devs[0]], [devs[1]]],
                                schedule="1f1b", n_micro=2, mb_keys=keys,
                                tied_ref=tied)
        grads, stats = runner.step(runner.place_params(sp), batch)
        assert abs(stats.loss - float(ref_loss)) < 1e-5, stats.loss
        def err(a, b):
            return max(float(jnp.max(jnp.abs(x - y)))
                       for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        errs = [err(grads[0]["lead"], ref_grads["lead"]),
                err(grads[0]["embed"], ref_grads["embed"]),
                err(grads[1]["blocks"], ref_grads["blocks"]),
                err(grads[1]["head"], ref_grads["head"])]
        assert max(errs) < 1e-5, errs
        print("PIPELINE_OK")
    """
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout
