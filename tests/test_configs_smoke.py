"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture runs one forward + one train step on CPU; output
shapes and finiteness asserted. Full configs are exercised only via the
dry-run (ShapeDtypeStructs, no allocation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, config_for_shape, get_config, get_reduced
from repro.launch import steps as steps_mod
from repro.models import decode_step, init_cache, init_params
from repro.optim.adam import AdamW
from repro.parallel.sharding import AxisRules


def _batch(cfg, B=2, S=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    b = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)}
    if cfg.frontend != "none":
        b["prefix"] = jax.random.normal(
            ks[2], (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    assert cfg.num_experts <= 4
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(params)
    step = jax.jit(steps_mod.make_train_step(cfg, opt, AxisRules()))
    new_params, new_opt, metrics = step(
        params, opt_state, jnp.asarray(0, jnp.int32), batch)
    assert jnp.isfinite(metrics["loss"])
    # params must change
    delta = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, new_params))
    assert max(delta) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B = 2
    cache = init_cache(cfg, B, 32)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = jax.jit(
        lambda p, c, t: decode_step(cfg, p, c, t, 3))(params, cache, tok)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # cache tree structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    spec = {
        "mamba2-130m": dict(num_layers=24, d_model=768, d_ff=0,
                            vocab_size=50280, ssm_state=128),
        "yi-6b": dict(num_layers=32, d_model=4096, num_heads=32,
                      num_kv_heads=4, d_ff=11008, vocab_size=64000),
        "olmoe-1b-7b": dict(num_layers=16, d_model=2048, num_heads=16,
                            num_kv_heads=16, d_ff=1024, vocab_size=50304,
                            num_experts=64, experts_per_token=8),
        "musicgen-large": dict(num_layers=48, d_model=2048, num_heads=32,
                               num_kv_heads=32, d_ff=8192, vocab_size=2048),
        "qwen2-1.5b": dict(num_layers=28, d_model=1536, num_heads=12,
                           num_kv_heads=2, d_ff=8960, vocab_size=151936,
                           qkv_bias=True),
        "deepseek-7b": dict(num_layers=30, d_model=4096, num_heads=32,
                            num_kv_heads=32, d_ff=11008, vocab_size=102400),
        "kimi-k2-1t-a32b": dict(num_layers=61, d_model=7168, num_heads=64,
                                num_kv_heads=8, d_ff=2048,
                                vocab_size=163840, num_experts=384,
                                experts_per_token=8),
        "jamba-v0.1-52b": dict(num_layers=32, d_model=4096, num_heads=32,
                               num_kv_heads=8, d_ff=14336, vocab_size=65536,
                               num_experts=16, experts_per_token=2),
        "internvl2-26b": dict(num_layers=48, d_model=6144, num_heads=48,
                              num_kv_heads=8, d_ff=16384, vocab_size=92553),
        "minitron-4b": dict(num_layers=32, d_model=3072, num_heads=24,
                            num_kv_heads=8, d_ff=9216, vocab_size=256000),
        "moonlight-16b-a3b": dict(num_layers=27, d_model=2048, num_heads=16,
                                  num_kv_heads=16, d_ff=1408,
                                  vocab_size=163840, num_experts=64,
                                  experts_per_token=6, kv_lora_rank=512,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                                  v_head_dim=128, shared_experts=2,
                                  first_dense_layers=1, dense_ff=11264,
                                  route_scale=2.446, router_score="sigmoid"),
    }[arch]
    for k, v in spec.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    assert cfg.source  # citation present


def test_long_context_variant_is_subquadratic():
    # dense archs get a sliding window for long_500k; SSM/hybrid unchanged
    assert config_for_shape("yi-6b", "long_500k").sliding_window == 8192
    assert config_for_shape("mamba2-130m", "long_500k").sliding_window == 0
    assert config_for_shape("yi-6b", "train_4k").sliding_window == 0


def test_param_counts_sane():
    # yi-6b ~6B, kimi ~1T total / ~32B active
    assert 5e9 < get_config("yi-6b").param_count() < 8e9
    assert 0.8e12 < get_config("kimi-k2-1t-a32b").param_count() < 1.3e12
    assert 15e9 < get_config("kimi-k2-1t-a32b").param_count(
        active_only=True) < 40e9
    assert 3e9 < get_config("minitron-4b").param_count() < 6e9
