"""What the harness finds by name, and what it refuses."""
import json
import os
from pathlib import Path
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _checkout(tmp_path, with_src=True):
    """A copy of what the benchmark needs: its manifest, its files and
    (optionally) the program."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_src:
        os.symlink(ROOT / "src", root / "src")
    return root


def test_new_config_traffic_metric_and_limits_are_files_only(tmp_path):
    root = _checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.*")}
    (root / "bench" / "configs" / "qwen2-1.5b-L4.json").write_text(json.dumps(
        {**json.loads((root / "bench/configs/qwen2-1.5b-L8.json").read_text()),
         "num_hidden_layers": 4}))
    (root / "bench" / "traffic" / "b4s2k.json").write_text(json.dumps(
        {"deployment": "single", "batch": 4, "seq": 2048, "loss_chunk": 512,
         "zipf": 1.1, "follow": 0.8, "pool": 4}))
    (root / "bench" / "limits" / "qwen2-1.5b-L4.b4s2k.json").write_text(
        json.dumps({"loss_gap": 0.5}))
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return ctx['tokens_per_s'] * 2\n")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "qwen2-1.5b-L4", "source": "x",
                                "file": "bench/configs/qwen2-1.5b-L4.json",
                                "reduced": ["num_hidden_layers"], "why": "x"})
    manifest["workloads"].append({"name": "qwen2-1.5b-L4.b4s2k",
                                  "config": "qwen2-1.5b-L4", "traffic": "b4s2k",
                                  "chips": 1, "why": "x"})
    manifest["per_layer"].append({"name": "steps_seen", "unit": "x",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "x", "moves": "tokens_per_s",
                                  "workloads": ["qwen2-1.5b-L4.b4s2k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = harness.load_cell("qwen2-1.5b-L4.b4s2k", root)
    assert cell.conf["num_hidden_layers"] == 4 and cell.mix["seq"] == 2048
    assert cell.limits == {"loss_gap": 0.5}
    assert cell.family.program_config(cell.conf).num_layers == 4
    assert [m["name"] for m in cell.per_layer] == ["mfu", "device_idle_share",
                                                   "steps_seen"]
    assert cell.reader("steps_seen").read({"tokens_per_s": 3.0}) == 6.0
    # the cells already there are untouched and still load
    assert harness.load_cell("qwen2-1.5b-L8.b2s4k", root).mix["batch"] == 2
    assert {p: p.read_bytes() for p in before} == before


def test_stage_spread_is_only_where_its_cell_lists_it():
    assert "stage_busy_spread" not in [
        m["name"] for m in harness.load_cell("qwen2-1.5b-L8.b2s4k").per_layer]
    assert "stage_busy_spread" in [
        m["name"] for m in harness.load_cell("qwen2-1.5b.pp4.b8s2k").per_layer]


def test_unknown_device_kind_is_an_error():
    from bench.peaks import peak
    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peak("cpu")


def _run(root, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b-L8.b2s4k",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    proc = _run(_checkout(tmp_path, with_src=False))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
