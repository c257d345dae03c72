"""Each fault a cell can have, planted under the timed path, and the
float8 control in the program's place, turn ``correct`` false under the
cells' own limits. The look for a chip is skipped; the rest of a run is
driven at a CPU size."""
import dataclasses

import pytest

from bench_tiny import (CONTROL_CONF, CONTROL_LIMITS, CONTROL_MIX, run_tiny,
                        tiny)
import bench_faults as faults


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "leaf_doubled"])
def test_one_chip_cell_faults(fault, monkeypatch, compile_cache):
    from repro.launch import steps as steps_mod
    broken = faults.single_step_faults(steps_mod.make_train_step)[fault]
    monkeypatch.setattr(steps_mod, "make_train_step", broken)
    line, _ = run_tiny(tiny("qwen2-1.5b-L8.b2s4k"))
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", ["qwen2-1.5b-L8.b2s4k",
                                  "qwen2-1.5b.pp4.b8s2k"])
def test_float8_control_fails(cell, compile_cache):
    c = dataclasses.replace(tiny(cell, CONTROL_CONF, CONTROL_MIX,
                                 CONTROL_LIMITS), chips=1)
    line, _ = run_tiny(c, system_factory=faults.ReferenceInPlace)
    assert line["correct"] is False, line["checks"]
    # the program itself, at the same size and limits, is correct
    c = dataclasses.replace(c, mix={**c.mix, "deployment": "single"})
    line, _ = run_tiny(c)
    assert line["correct"] is True, line["checks"]
