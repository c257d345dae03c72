"""The model-FLOP counts of the benchmark's configurations against counts
made by hand from the published shapes."""
import json
from pathlib import Path

import pytest

from bench.families import dense, mamba2

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _qwen_by_hand(layers, seq):
    # per layer: q 1536x1536, k and v 1536x256, o 1536x1536, MLP 3 x 1536x8960
    per_layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    head = 151936 * 1536                       # tied: the head counts once
    attention = 6 * 12 * 128 * (seq + 1)       # 3 x 4 x H x hd x (S+1)/2
    return 6 * (layers * per_layer + head) + layers * attention


@pytest.mark.parametrize("name,layers,seq,matmul_params", [
    ("qwen2-1.5b-L8", 8, 4096, 607_715_328),
    ("qwen2-1.5b", 28, 2048, 1_543_569_408),
])
def test_dense_flops(name, layers, seq, matmul_params):
    conf = _conf(name)
    assert conf["num_hidden_layers"] == layers
    got = dense.flops_per_token(conf, seq)
    assert got == _qwen_by_hand(layers, seq)
    # the matmul part is 6 x the parameters that multiply, head included
    attention = layers * 6 * 12 * 128 * (seq + 1)
    assert (got - attention) / 6 == matmul_params


def test_mamba2_flops():
    conf = _conf("mamba2-130m")
    assert mamba2.padded_vocab(conf) == 50288
    # in_proj 768 x (2*1536 + 2*128 + 24), out_proj 1536 x 768, tied head
    matmul = 24 * (768 * 3352 + 1536 * 768) + 50288 * 768
    assert matmul == 128_716_800
    # SSD forward per token and head at Q = 256: C.B and its weighting of
    # x over (Q+1)/2 positions, state update and read-out: 2 FLOPs a MAC
    Q, ds, hd, nh = 256, 128, 64, 24
    ssd = nh * (2 * ds * (Q + 1) / 2 + 2 * hd * (Q + 1) / 2 + 2 * 2 * hd * ds)
    expected = 6 * matmul + 3 * 24 * ssd
    assert mamba2.flops_per_token(conf, 4096) == pytest.approx(expected, rel=1e-12)
    # the count names its own chunk and does not follow the program's
    assert mamba2.flops_per_token(dict(conf, chunk_size=128), 4096) \
        == mamba2.flops_per_token(conf, 4096)


def test_flops_ignore_recomputation_and_sequence_only_in_attention():
    conf = _conf("qwen2-1.5b-L8")
    short, long = (dense.flops_per_token(conf, s) for s in (1024, 4096))
    assert long - short == 8 * 6 * 12 * 128 * (4096 - 1024)
