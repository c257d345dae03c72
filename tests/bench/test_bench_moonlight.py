"""The Moonlight family at CPU sizes: the program against the plain
reference over the checked steps; each chip's share of the expert layer
adding up to the uncut layer; the model-FLOP count; the readers of the
``moe`` scope; and the per-layer metrics of the cell added with it."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, check, harness, traffic
from bench.families import moonlight
from bench.metrics import expert_matmul_share
from bench_tiny import TINY_LIMITS

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
NAME = "moonlight-16b-a3b-ep8-L5"
# every mechanism at CPU widths: 1 dense + 2 MoE layers, 2 of 8 experts
# held, top-3, one shared expert
SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 2,
         "num_experts_per_tok": 3, "n_shared_experts": 1,
         "num_hidden_layers": 3, "vocab_size": 256,
         "deployment": {"expert_shards": 4, "expert_shard": 0}}
HI = jax.lax.Precision.HIGHEST


def _conf(**over):
    return {**json.loads((CONFIGS / f"{NAME}.json").read_text()), **SMALL,
            "name": NAME, **over}


def test_program_agrees_with_reference(compile_cache):
    conf = _conf()
    mix = {"deployment": "single", "batch": 2, "seq": 64, "loss_chunk": 32,
           "zipf": 1.1, "follow": 0.8, "pool": 0}
    cell = harness.Cell(NAME, 1, conf, mix, TINY_LIMITS)
    devices = jax.devices()[:1]
    seed = 2**31 + 5
    key = harness.weight_key(seed)
    vocab = cell.family.program_config(conf).vocab_size
    checked = traffic.batches(mix, vocab, seed)[:traffic.CHECKED_STEPS]
    prog = calibrate.program_readings(cell, devices, key, checked)
    ref = harness.reference_readings(cell, devices, key, checked)
    got = check.compare(prog, ref, TINY_LIMITS)
    assert all(c["value"] <= c["limit"] for c in got.values()), got


def test_program_agrees_with_reference_at_even_share(compile_cache):
    """As the cell runs it: the held experts offset to their even share
    of each batch's choices, in the program and in the reference."""
    conf = _conf(deployment={"expert_shards": 4, "expert_shard": 0,
                             "even_share": True})
    assert moonlight.program_config(conf).even_share
    mix = {"deployment": "single", "batch": 2, "seq": 64, "loss_chunk": 32,
           "zipf": 1.1, "follow": 0.8, "pool": 0}
    cell = harness.Cell(NAME, 1, conf, mix, TINY_LIMITS)
    devices = jax.devices()[:1]
    seed = 2**31 + 11
    key = harness.weight_key(seed)
    vocab = cell.family.program_config(conf).vocab_size
    checked = traffic.batches(mix, vocab, seed)[:traffic.CHECKED_STEPS]
    prog = calibrate.program_readings(cell, devices, key, checked)
    ref = harness.reference_readings(cell, devices, key, checked)
    got = check.compare(prog, ref, TINY_LIMITS)
    assert all(c["value"] <= c["limit"] for c in got.values()), got


@pytest.mark.parametrize("E,shards,K,shard", [(8, 4, 3, 0), (8, 4, 3, 2),
                                              (64, 8, 6, 0), (4, 2, 2, 1)])
def test_even_share_offset_matches_the_reference(E, shards, K, shard):
    """The program's offset and the reference's, on the same biased
    scores, are equal and give the held experts T K held / E choices."""
    from repro.models import moe
    T, Eh = 384, E // shards
    b = (jax.nn.sigmoid(2 * jax.random.normal(jax.random.PRNGKey(E), (T, E)))
         + jnp.linspace(0.3, 0.0, E))      # skewed, as an untrained router
    held = np.arange(E) // Eh == shard
    got = moe.even_share_offset(b, shard * Eh, Eh, K)
    want = moonlight._even_offset(b, held, K)
    assert float(got) == pytest.approx(float(want), abs=1e-7)
    _, idx = jax.lax.top_k(b + jnp.where(held, got, 0.0), K)
    assert int(np.isin(np.asarray(idx), np.flatnonzero(held)).sum()) == \
        T * K * Eh // E


def _uncut_layer(E=8, shards=4):
    """An MoE layer's parameters with all E experts, a non-zero bias."""
    conf = _conf(n_routed_experts=E,
                 deployment={"expert_shards": 1, "expert_shard": 0})
    cfg = moonlight.program_config(conf).replace(dtype="float32")
    f = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     moonlight.make_params(conf, jax.random.PRNGKey(3))
                     ["blocks"]["layer0"]["ffn"])
    f["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (E,))
    return conf, cfg, f


@pytest.mark.parametrize("E,shards", [(8, 4), (4, 2)])
def test_shares_add_up_to_the_uncut_layer(E, shards):
    """For every shard index, the held experts' part of the layer; the
    parts summed over the shards, with the shared expert counted once,
    are the uncut reference layer with all experts."""
    from repro.models import moe
    from repro.models.layers import mlp_fwd
    conf, cfg, f = _uncut_layer(E, shards)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
    Eh = E // shards
    total = -(shards - 1) * mlp_fwd(f["shared"], x)
    for i in range(shards):
        part = dict(f, **{n: f[n][i * Eh:(i + 1) * Eh]
                          for n in ("w_gate", "w_up", "w_down")})
        y, _ = moe.dropless_fwd(cfg.replace(expert_shards=shards), part, x,
                                shard=i)
        total = total + y
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HI)  # noqa: E731
    ref = moonlight._moe(conf, f, x, mm)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_every_token_on_held_experts_is_computed():
    """Every token routed to the same two experts, both held here: each
    of its assignments is computed (the capacity path would keep 1.25 x
    K / E of them), as the reference computes them."""
    from repro.models import moe
    conf, cfg, f = _uncut_layer(8, 4)
    f["router_bias"] = jnp.zeros((8,)).at[2:4].set(10.0)   # shard 1's
    conf = dict(conf, num_experts_per_tok=2, n_routed_experts=2,
                deployment={"expert_shards": 4, "expert_shard": 1})
    cfg = cfg.replace(experts_per_token=2, expert_shards=4)
    part = dict(f, **{n: f[n][2:4] for n in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
    y, stats = moe.dropless_fwd(cfg, part, x, shard=1)
    assert int(stats["moe_rows_here"]) == 2 * 32 * 2
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HI)  # noqa: E731
    ref = moonlight._moe(conf, part, x, mm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_moonlight_flops():
    conf = json.loads((CONFIGS / f"{NAME}.json").read_text())
    assert conf["num_hidden_layers"] == 5 and conf["n_routed_experts"] == 8
    assert conf["vocab_size"] == 20480 and conf["deployment"]["expert_shards"] == 8
    # per layer: q 2048 x 16*192, latent 2048 x 576, kv 512 x 16*256,
    # o 2048 x 2048; dense FFN 3 x 2048 x 11264; per MoE layer the router
    # 2048 x 64, 6 x 8 / 64 of a 3 x 2048 x 1408 expert, shared 3 x 2048
    # x 2816; head 20480 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560
    matmul = (attn + 3 * 2048 * 11264 + 4 * (
        attn + 2048 * 64 + 0.75 * 3 * 2048 * 1408 + 3 * 2048 * 2816)
        + 20480 * 2048)
    assert matmul == 275_644_416
    attention = 5 * 3 * 16 * (192 + 128) * (8192 + 1)
    got = moonlight.flops_per_token(conf, 8192)
    assert got == pytest.approx(6 * matmul + attention, rel=1e-12)
    assert got == pytest.approx(2.283e9, rel=1e-3)


def test_program_layout_and_sizes():
    """The cell's program: 1 dense + 4 MoE layers, the router at 64
    outputs, top-6, 8 experts held, 2 shared, every width as published."""
    conf = json.loads((CONFIGS / f"{NAME}.json").read_text())
    cfg = moonlight.program_config(dict(conf, name=NAME))
    assert (cfg.first_dense_layers, cfg.num_periods) == (1, 4)
    assert (cfg.num_experts, cfg.held_experts, cfg.experts_per_token) == (64, 8, 6)
    assert (cfg.shared_experts, cfg.d_ff, cfg.dense_ff) == (2, 1408, 11264)
    assert (cfg.kv_lora_rank, cfg.qk_head_dim, cfg.v_head_dim) == (512, 192, 128)
    assert cfg.vocab_size == 20480 and cfg.dropless
    assert cfg.param_count() == 568_482_560
    tree = jax.eval_shape(lambda k: moonlight.make_params(conf, k),
                          jax.random.PRNGKey(0))
    from repro.models import abstract_params
    assert jax.tree.structure(tree) == jax.tree.structure(abstract_params(cfg))


def test_expert_ops_are_the_experts_sub_scope():
    text = "\n".join([
        '%fusion.1 = bf16[8,4]{1,0} fusion(), metadata={op_name="jit(step)/loss/moe/experts/ragged_dot"}',
        '%fusion.2 = bf16[8,4]{1,0} fusion(), metadata={op_name="jit(step)/transpose(jvp(loss))/moe/while/body/experts/ragged_dot"}',
        '%gather.3 = bf16[8,4]{1,0} gather(), metadata={op_name="jit(step)/loss/moe/dispatch/gather"}',
        '%fusion.4 = bf16[8,4]{1,0} fusion(), metadata={op_name="jit(step)/loss/mlp/experts"}',
    ])
    from bench import spans
    scopes = spans.scopes_of(text)
    assert expert_matmul_share.expert_ops(text, scopes) == {"fusion.1", "fusion.2"}


@pytest.mark.parametrize("cell,metrics", [
    ("qwen2-1.5b-L8.b8s1k", ["mfu", "device_idle_share", "attention_share"]),
    ("moonlight-16b-a3b-ep8-L5.b4s8k",
     ["mfu", "device_idle_share", "attention_share", "moe_share",
      "expert_matmul_share"]),
])
def test_added_cells_metrics(cell, metrics):
    assert [m["name"] for m in harness.load_cell(cell).per_layer] == metrics


def test_program_config_takes_the_first_share():
    """The one-chip program computes the first share of the experts; a
    configuration that names another share is refused, not run as the
    first."""
    cfg = moonlight.program_config(_conf())
    assert (cfg.expert_shards, cfg.held_experts, cfg.num_experts) == (4, 2, 8)
    with pytest.raises(ValueError, match="first share"):
        moonlight.program_config(
            _conf(deployment={"expert_shards": 4, "expert_shard": 1}))
