"""End-to-end behaviour tests: training converges on the synthetic bigram
task; serving generates; TAG's full pipeline produces a deployable plan."""
import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core.device import tpu_pods
from repro.core.plan import lower_strategy
from repro.core.tag import optimize
from repro.launch.serve import generate
from repro.launch.train import main as train_main
from repro.models import init_params, loss_fn
from repro.parallel.sharding import AxisRules


def test_training_loss_decreases_e2e():
    losses = train_main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "12",
                         "--batch", "8", "--seq", "64",
                         "--log-every", "100"])
    assert losses[-1] < losses[0] - 0.5


def test_checkpoint_resume_continues(tmp_path):
    d = str(tmp_path / "ck")
    train_main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "4",
                "--batch", "4", "--seq", "32", "--ckpt-dir", d,
                "--ckpt-every", "4", "--log-every", "100"])
    losses = train_main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "8",
                         "--batch", "4", "--seq", "32", "--ckpt-dir", d,
                         "--resume", "--log-every", "100"])
    assert len(losses) == 4   # resumed from step 4


def test_run_single_on_one_device_times_each_step():
    import math
    from repro.launch.train import build_parser, run_single
    args = build_parser().parse_args(
        ["--steps", "3", "--batch", "2", "--seq", "32", "--loss-chunk",
         "16", "--log-every", "100"])
    run = run_single(args, get_reduced("qwen2-1.5b"),
                     devices=jax.devices()[:1])
    assert len(run.losses) == len(run.grad_norms) == len(run.step_s) == 3
    assert all(math.isfinite(v) for v in run.losses + run.grad_norms)
    assert all(t > 0 for t in run.step_s)
    assert jax.tree.leaves(run.params)[0].devices() == {jax.devices()[0]}


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own read
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:    # later tests in this process compile without the cache
        jax.config.update("jax_compilation_cache_dir", before)


def test_serving_generates_tokens():
    cfg = get_reduced("jamba-v0.1-52b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jnp.ones((2, 4), jnp.int32)
    out = generate(cfg, params, prompts, 6, AxisRules())
    assert out.shape == (2, 6)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab_size).all())


def test_tag_full_pipeline_on_reduced_arch():
    """Trace one of the ASSIGNED architectures (reduced) through TAG and
    lower the strategy to an execution plan."""
    cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
    topo = tpu_pods()
    res = optimize(lambda p, b: loss_fn(cfg, p, b, remat=False)[0],
                   params, batch, topo, name="qwen2", iterations=12,
                   n_groups=16, seed=0)
    assert res.search.best_reward >= 1.0 - 1e-9
    assert res.strategy.complete()

    class _Mesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}
    plan = lower_strategy(res.strategy, res.gg, topo, _Mesh())
    assert plan.rules.rules["batch"] in (("pod", "data"), ("data",))
    assert set(plan.grad_sync.values()) <= {"allreduce", "ps", "sfb"}
