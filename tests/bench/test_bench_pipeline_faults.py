"""The pipeline cell's faults, planted under the timed path in a process
with four CPU devices: a step that returns its state unchanged, half the
batch left out, one leaf moved twice, and the tied head's gradient left
out of the exchange between stages. Each turns ``correct`` false; the
sound run stays correct."""
import json
import os
from pathlib import Path
import subprocess
import sys

HERE = Path(__file__).resolve().parent


def test_pipeline_cell_faults(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")])
    proc = subprocess.run([sys.executable, str(HERE / "bench_faults.py")], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "state_unchanged": False,
                   "half_batch": False, "leaf_doubled": False,
                   "exchange_left_out": False}
