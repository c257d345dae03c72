"""Each family's weights fit the program's parameter layout, and the program
agrees with the family's plain reference over the checked steps at a CPU
size (for Mamba-2, a sequence short enough that no chunk's decay sum
overflows)."""
import json
from pathlib import Path

import jax
import pytest

from bench import calibrate, check, harness, traffic
from bench_tiny import TINY_LIMITS

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
SIZES = {
    "qwen2-1.5b-L8": ({"hidden_size": 64, "intermediate_size": 128,
                       "num_attention_heads": 4, "num_key_value_heads": 2,
                       "num_hidden_layers": 2, "vocab_size": 256}, 64),
    "mamba2-130m": ({"d_model": 64, "n_layer": 2, "vocab_size": 250,
                     "d_state": 16, "headdim": 16, "chunk_size": 16}, 32),
}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_program_agrees_with_reference(name, compile_cache):
    size, seq = SIZES[name]
    conf = {**json.loads((CONFIGS / f"{name}.json").read_text()), **size,
            "name": name}
    mix = {"deployment": "single", "batch": 2, "seq": seq, "loss_chunk": 32,
           "zipf": 1.1, "follow": 0.8, "pool": 0}
    cell = harness.Cell(name, 1, conf, mix, TINY_LIMITS)
    devices = jax.devices()[:1]
    seed = 2**31 + 5
    key = harness.weight_key(seed)
    vocab = cell.family.program_config(conf).vocab_size
    checked = traffic.batches(mix, vocab, seed)[:traffic.CHECKED_STEPS]
    prog = calibrate.program_readings(cell, devices, key, checked)
    ref = harness.reference_readings(cell, devices, key, checked)
    got = check.compare(prog, ref, TINY_LIMITS)
    assert all(c["value"] <= c["limit"] for c in got.values()), got


def test_mamba_runs_the_published_chunk():
    conf = {**json.loads((CONFIGS / "mamba2-130m.json").read_text()),
            "name": "mamba2-130m"}
    from bench.families import mamba2
    cfg = mamba2.program_config(conf)
    assert cfg.ssm_chunk == conf["chunk_size"] == 256
    assert cfg.vocab_size == 50288 and cfg.tie_embeddings
    assert cfg.num_layers == 24
