"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret mode executes the kernel bodies on
CPU)."""
import json
import os
from pathlib import Path
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import gqa_flash_attention, mamba_ssd
from repro.kernels.ref import ref_attention, ref_ssd
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("S,hd,bq,bk", [
    (128, 64, 64, 64),
    (256, 64, 128, 64),
    (256, 32, 64, 128),
    (128, 128, 128, 128),
])
def test_flash_attention_causal(S, hd, bq, bk, dtype, atol):
    B, H = 2, 2
    q, k, v = (_rand((B, H, S, hd), dtype) for _ in range(3))
    o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    r = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=atol)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window):
    B, H, S, hd = 1, 2, 256, 32
    q, k, v = (_rand((B, H, S, hd), jnp.float32) for _ in range(3))
    o = flash_attention(q, k, v, causal=True, window=window,
                        block_q=64, block_k=64)
    r = ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_flash_attention_noncausal():
    B, H, S, hd = 1, 1, 128, 64
    q, k, v = (_rand((B, H, S, hd), jnp.float32) for _ in range(3))
    o = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    r = ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_gqa_wrapper_matches_model_attention():
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q = _rand((B, S, H, hd), jnp.float32)
    k = _rand((B, S, KV, hd), jnp.float32)
    v = _rand((B, S, KV, hd), jnp.float32)
    o = gqa_flash_attention(q, k, v, block_q=64, block_k=64)
    # reference: expand kv then full attention
    G = H // KV
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    r = ref_attention(q.transpose(0, 2, 1, 3), kh, vh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-4),
                                        (jnp.bfloat16, 1e-1)])
@pytest.mark.parametrize("S,nh,hd,ds,chunk", [
    (128, 2, 32, 16, 64),
    (256, 4, 64, 32, 128),
    (192, 1, 16, 8, 64),
])
def test_ssd_scan_vs_naive_recurrence(S, nh, hd, ds, chunk, dtype, atol):
    Bb = 2
    x = _rand((Bb, S, nh, hd), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (Bb, S, nh)), dtype)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (nh,)), jnp.float32)
    Bm = _rand((Bb, S, nh, ds), dtype)
    Cm = _rand((Bb, S, nh, ds), dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, _ = ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol)


def test_ssd_kernel_matches_model_chunked_path():
    from repro.models.ssm import ssd_chunked
    Bb, S, nh, hd, ds = 1, 128, 2, 32, 16
    x = _rand((Bb, S, nh, hd), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (Bb, S, nh)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (nh,)), jnp.float32)
    Bm = _rand((Bb, S, nh, ds), jnp.float32)
    Cm = _rand((Bb, S, nh, ds), jnp.float32)
    y = mamba_ssd(x, dt, A, Bm, Cm, chunk=64)
    y2, _ = ssd_chunked(x, dt, A, Bm, Cm, 64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-4)


@pytest.mark.parametrize("S,window", [(128, 0), (256, 0), (256, 64)])
def test_splash_attention_grads_match_jnp_attention(S, window):
    """Loss and dq/dk/dv through the trainable kernel (interpreted here)
    against the model's jnp query-chunk scan: causal, GQA with G = 3."""
    import jax
    from repro.configs import get_reduced
    from repro.kernels.ops import gqa_splash_attention
    from repro.models.attention import chunk_attention
    cfg = get_reduced("qwen2-1.5b").replace(sliding_window=window,
                                             attn_chunk=64)
    B, H, KV, hd = 2, 6, 2, 32
    q, do = (_rand((B, S, H, hd), jnp.float32) for _ in range(2))
    k, v = (_rand((B, S, KV, hd), jnp.float32) for _ in range(2))

    def loss(attn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v) * do), argnums=(0, 1, 2))
    got, got_g = loss(lambda q, k, v: gqa_splash_attention(
        q, k, v, window=window))(q, k, v)
    want, want_g = loss(lambda q, k, v: chunk_attention(
        cfg, q, k, v, jnp.arange(S)))(q, k, v)
    # a sum of ~1e5 products of order 1: f32 summation order alone moves it
    np.testing.assert_allclose(float(got), float(want), atol=1e-3)
    for g, w in zip(got_g, want_g, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4)


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_backend(monkeypatch, backend, expect):
    import jax
    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is expect
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


@pytest.mark.parametrize("impl,backend,seq,window,expect", [
    ("auto", "tpu", 4096, 0, "pallas"),
    ("auto", "tpu", 2048, 0, "pallas"),
    ("auto", "cpu", 4096, 0, "jnp"),
    ("auto", "tpu", 4096, 4096, "jnp"),       # sliding window
    ("auto", "tpu", 4000, 0, "jnp"),          # no block size tiles it
    ("auto", "tpu", 1024, 0, "jnp"),          # below the tuned blocks
    ("auto", "tpu", 64, 0, "jnp"),
    ("jnp", "tpu", 4096, 0, "jnp"),
    ("pallas", "cpu", 4096, 4096, "pallas"),
])
def test_attn_impl_auto_follows_backend(monkeypatch, impl, backend, seq,
                                        window, expect):
    import jax
    from repro.kernels.ops import resolve_attn_impl
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_attn_impl(impl, seq, window) == expect


@pytest.mark.parametrize("axes,expect", [
    (None, ()),                                   # no sharding rules
    ({"data": 2, "model": 1}, "data"),            # batch sharded only
    ({"data": 1, "model": 1}, ()),
    ({"data": 2, "model": 2}, None),              # heads sharded: jnp
])
def test_kernel_runs_per_batch_shard_only(axes, expect):
    from jax.sharding import AbstractMesh
    from repro.launch.steps import baseline_rules
    from repro.models.attention import _kernel_batch_axis
    from repro.parallel.sharding import axis_rules
    q = jnp.zeros((4, 128, 12, 32))
    k = jnp.zeros((4, 128, 2, 32))
    if axes is None:
        assert _kernel_batch_axis(q, k) == expect
        return
    mesh = AbstractMesh(tuple(axes.values()), tuple(axes))
    with axis_rules(baseline_rules(mesh)):
        assert _kernel_batch_axis(q, k) == expect


def test_kernel_under_a_batch_sharded_mesh_trains_as_jnp():
    """Two CPU devices, batch over "data": the kernel runs inside
    shard_map per batch shard, and the loss and gradients match the jnp
    path under the same rules."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.launch import mesh as mesh_mod, steps as steps_mod
        from repro.models import init_params, loss_fn
        from repro.parallel.sharding import axis_rules
        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        rules = steps_mod.baseline_rules(mesh_mod.make_host_mesh(jax.devices()))
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}

        def run(impl):
            c = cfg.replace(attn_impl=impl)
            def f(p):
                with axis_rules(rules):
                    return loss_fn(c, p, batch, remat=False)[0]
            fn = jax.jit(jax.value_and_grad(f))
            return fn(params), fn.lower(params).as_text()
        (l_k, g_k), text = run("pallas")
        (l_j, g_j), _ = run("jnp")
        gap = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                  for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_j)))
        print(json.dumps({"loss": [float(l_k), float(l_j)], "gap": gap,
                          "shard_map": "sdy.manual_computation" in text}))
    """)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["shard_map"]
    np.testing.assert_allclose(*got["loss"], rtol=1e-5)
    assert got["gap"] < 1e-4
