"""Readings that the correctness limits are set from, on the chip, in one
process per cell (set-up is paid once):

    python bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        --out <file.jsonl>

For each seed the cell's program is built from that seed and driven
through the checked steps exactly as a benchmark run drives them, its
state freed, and the reference repeated: that gives the lower readings.
On the first ``--control-seeds`` seeds it also reads, against the same
reference,

* ``control``: the reference computed in float8 (``precision="fp8"``) in
  the program's place;
* ``half_batch``: the reference with the second half of every batch
  replaced by the first, so the mean is over half the rows.

A step that returns its state unchanged reads 1 on ``change_gap`` by
definition, and one leaf moved twice reads 1 on it too: neither needs a
run. The benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys
import time

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, traffic  # noqa: E402


def program_readings(cell, devices, key, checked) -> dict:
    system = harness.make_system(cell, devices)
    system.init(key)
    out = harness.checked_steps(system, checked, key)
    system.free()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.tpu_devices(cell.chips)
    harness.enable_compile_cache()
    vocab = cell.family.program_config(cell.conf).vocab_size
    B = cell.mix["batch"]
    half = [i % (B // 2) for i in range(B)]
    with open(args.out, "a") as f:
        for n in range(args.seeds):
            seed = args.first_seed + 7919 * n
            t = time.perf_counter()
            key = harness.weight_key(seed)
            checked = traffic.batches(cell.mix, vocab, seed)[:traffic.CHECKED_STEPS]
            prog = program_readings(cell, devices, key, checked)
            ref = harness.reference_readings(cell, devices, key, checked)
            rec = {"cell": cell.name, "seed": seed, "program": {
                k: v[0] for k, v in check.readings(prog, ref).items()},
                "program_losses": prog["losses"], "ref_losses": ref["losses"]}
            if n < args.control_seeds:
                for name, kw in (("control", {"precision": "fp8"}),
                                 ("half_batch", {"rows": half})):
                    other = harness.reference_readings(
                        cell, devices, key, checked, **kw)
                    rec[name] = {k: v[0] for k, v in
                                 check.readings(other, ref).items()}
            rec["seconds"] = time.perf_counter() - t
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
