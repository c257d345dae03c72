"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret mode executes the kernel bodies on
CPU)."""
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


def test_flash_attention_grad_raises_plainly():
    """The kernel is forward-only: training through attn_impl='pallas'
    must say so, not fail inside Pallas' autodiff."""
    import jax
    from repro.configs import get_reduced
    from repro.models import init_params, loss_fn
    cfg = get_reduced("qwen2-1.5b").replace(attn_impl="pallas",
                                             dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.ones((1, 128), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    loss, _ = loss_fn(cfg, params, batch, remat=False)   # forward runs
    assert np.isfinite(float(loss))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda p: loss_fn(cfg, p, batch, remat=False)[0])(params)


@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_backend(monkeypatch, backend, expect):
    import jax
    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is expect
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
