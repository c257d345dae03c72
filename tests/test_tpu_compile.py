"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a topology
that is only described. It refuses what the chip would refuse: a kernel
block that does not tile, a program that does not fit in HBM. Nothing
here runs, so it says nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
each import every test file. Keep these tests in this one file.
"""
import importlib.util
import os
from pathlib import Path
import re

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding
import pytest

from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import gqa_splash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.models import abstract_params, input_specs
from repro.models.attention import chunk_attention
from repro.optim.adam import AdamW

V5E_HBM_BYTES = int(15.75 * 2**30)   # what XLA lets one program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_flash_attention_compiles_for_v5e(one_chip):
    """Forward at qwen2-1.5b's heads (12 x 128) over a 2k sequence."""
    cfg = get_config("qwen2-1.5b")
    shape = (1, cfg.num_heads, 2048, cfg.resolved_head_dim)
    q = _spec(shape, jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_splash_attention_trains_on_v5e(one_chip):
    """Forward and backward of the trainable kernel at the one-chip cell's
    shape (batch 2, S 4096, 12 q / 2 kv heads of 128): the kernels are
    the program, and the f32 score tiles of the jnp scan, (.., 1024,
    4096) a query chunk, are gone with their temporaries."""
    cfg = get_config("qwen2-1.5b")
    B, S, H, KV, hd = 2, 4096, 12, 2, 128
    q = _spec((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, KV, hd), jnp.bfloat16, one_chip)
    tile = re.compile(r"f32\[[\d,]*1024,4096\]")

    def compiled(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    def scoped(q, k, v):
        with jax.named_scope("attention"):
            return gqa_splash_attention(q, k, v, interpret=False)
    kernel = compiled(scoped)
    text = kernel.as_text()
    # the forward (with residuals) and the fused dq/dk/dv backward, each
    # instruction on one line with its scope, as tools that read the
    # compiled text line by line find it
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) >= 2
    assert all(re.search(r'metadata=\{op_name="[^"]*attention', ln) for ln in calls)
    assert not tile.search(text)
    # a few (B, H, S, hd) arrays (the f32 dq accumulator is 25 MB), not
    # the scan's gigabytes of score tiles
    assert kernel.memory_analysis().temp_size_in_bytes < 64 * 2**20
    scan = compiled(lambda q, k, v: chunk_attention(cfg, q, k, v,
                                                     jnp.arange(S)))
    assert tile.search(scan.as_text())
    assert scan.memory_analysis().temp_size_in_bytes > 2**30


def test_ssd_scan_compiles_for_v5e(one_chip):
    """At mamba2-130m's heads (24 x 64, state 128, chunk 128)."""
    cfg = get_config("mamba2-130m")
    Bb, S = 1, 2048
    nh, hd, ds = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    args = (_spec((Bb, S, nh, hd), jnp.bfloat16, one_chip),
            _spec((Bb, S, nh), jnp.float32, one_chip),
            _spec((nh,), jnp.float32, one_chip),
            _spec((Bb, S, nh, ds), jnp.bfloat16, one_chip),
            _spec((Bb, S, nh, ds), jnp.bfloat16, one_chip))
    compiled = jax.jit(
        lambda *a: ssd_scan(*a, chunk=cfg.ssm_chunk, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_train_step_fits_v5e(topo):
    """The donated single-mesh step of ``chip_smoke.py``'s one-chip phase
    (qwen2-1.5b widths, depth cut, AdamW fp32 moments), built as
    ``launch.train.run_single`` builds it, fits one chip's HBM."""
    smoke = _chip_smoke()
    size = smoke.ONE_CHIP
    cfg = smoke.model_config()
    mesh = mesh_mod.make_host_mesh(topo.devices[:1])
    rules = steps_mod.baseline_rules(mesh)
    opt = AdamW()
    rep = NamedSharding(mesh, P())

    def shard(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, rep), tree)

    params = abstract_params(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    batch = input_specs(cfg, InputShape("smoke", size["seq"], size["batch"],
                                        "train"))
    step = jax.jit(steps_mod.make_train_step(
        cfg, opt, rules, steps_mod.StepOptions(loss_chunk=size["loss_chunk"])),
        donate_argnums=(0, 1))
    compiled = step.lower(shard(params), shard(opt_state),
                          _spec((), jnp.int32, rep), shard(batch)).compile()
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves((params, opt_state)))
    # donated: params and moments are updated in place, not copied (the
    # chip's tiled layouts pad a few small arrays, hence >=)
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    assert _total_bytes(compiled) <= V5E_HBM_BYTES
