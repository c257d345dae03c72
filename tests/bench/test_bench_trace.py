"""The reduction from a profiler trace to busy time, idle share, stage
spread and the breakdown."""
from pathlib import Path

import pytest

from bench import trace
from bench.metrics import device_idle_share, stage_busy_spread

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _constructed():
    # window 0..100 ms; device 0 busy 10-30 (two overlapping ops) and
    # 50-60; device 1 busy 0-80
    return {
        "host": [("bench_window", 0, 100 * MS), ("dispatch", 0, 10 * MS),
                 ("wait", 10 * MS, 70 * MS), ("device_put", 80 * MS, 20 * MS)],
        "devices": {
            0: [("%fusion.1 = bf16[2] fusion()", 10 * MS, 15 * MS),
                ("%fusion.2 = bf16[2] fusion()", 20 * MS, 10 * MS),
                ("%convolution.3 = bf16[2] convolution()", 50 * MS, 10 * MS),
                ("%fusion.4 = x", 150 * MS, 10 * MS)],        # outside
            1: [("%copy.7 = x", -10 * MS, 90 * MS)],          # clipped
        },
    }


def test_busy_union_clipped_to_window():
    red = trace.reduce(_constructed(), [0, 1])
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"][0] == pytest.approx(0.030)
    assert red["busy_s"][1] == pytest.approx(0.080)


def test_idle_share_and_stage_spread():
    red = trace.reduce(_constructed(), [0, 1])
    ctx = {"busy_s": [red["busy_s"][0], red["busy_s"][1]],
           "window_s": red["window_s"]}
    assert device_idle_share.read(ctx) == pytest.approx(100 * (1 - 0.055 / 0.1))
    assert stage_busy_spread.read(ctx) == pytest.approx(100 * (0.08 - 0.03) / 0.08)
    assert stage_busy_spread.read({"busy_s": [0.5]}) is None
    assert device_idle_share.read({"busy_s": [0.0], "window_s": 1.0}) is None


def test_breakdown_groups_and_gaps():
    red = trace.reduce(_constructed(), [0, 1],
                       op_labels={"fusion.1": "layer/mlp", "fusion.2": "layer/mlp"})
    ops = dict(red["device_ops"])
    assert ops["layer/mlp"] == pytest.approx(0.025)      # 15 + 10 ms
    assert ops["convolution"] == pytest.approx(0.010)    # suffix dropped
    assert ops["copy"] == pytest.approx(0.080)
    gaps = red["idle_gaps"]
    assert gaps[0] == ("device_put (device 0)", pytest.approx(0.040))  # 60-100
    assert ("wait (device 0)", pytest.approx(0.020)) in gaps           # 30-50
    assert ("device_put (device 1)", pytest.approx(0.020)) in gaps
    assert ("dispatch (device 0)", pytest.approx(0.010)) in gaps


def test_hlo_labels_name_the_pass_and_drop_wrappers():
    text = "\n".join(
        f'%{ins} = f32[2] fusion(%a), kind=kLoop, metadata={{op_name="{op}" '
        'stack_frame_id=3}' for ins, op in [
            ("fusion.9", "jit(train_step)/jit(main)/transpose(jvp(loss))/"
                         "while/body/closed_call/mlp/dot_general"),
            ("fusion.10", "jit(train_step)/transpose(jvp())/while/body/"
                          "closed_call/checkpoint/rematted_computation/exp"),
            ("fusion.11", "jit(train_step)/jvp()/while/body/dot_general"),
            ("add.3", "jit(train_step)/add")])
    assert trace.hlo_op_labels(text) == {
        "fusion.9": "bwd mlp/dot_general", "fusion.10": "recompute exp",
        "fusion.11": "fwd dot_general", "add.3": "step add"}


def test_nested_ops_count_their_own_time_once():
    # a loop op 0-60 ms whose body ops run 10-30 and 40-50; a second op
    # 70-90 that starts inside the window and ends after it (80 ms)
    ev = {"host": [("bench_window", 0, 80 * MS)],
          "devices": {0: [("%while.1 = x", 0, 60 * MS),
                          ("%fusion.2 = x", 10 * MS, 20 * MS),
                          ("%fusion.3 = x", 40 * MS, 10 * MS),
                          ("%copy.4 = x", 70 * MS, 20 * MS)]}}
    red = trace.reduce(ev, [0])
    ops = dict(red["device_ops"])
    assert ops["while"] == pytest.approx(0.030)
    assert ops["fusion"] == pytest.approx(0.030)
    assert ops["copy"] == pytest.approx(0.010)
    assert sum(ops.values()) == pytest.approx(red["busy_s"][0])


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: five steps of a jitted matmul
    program under the harness's spans."""
    ev = trace.events(str(DATA / "v5e_matmul.xplane.pb"))
    assert list(ev["devices"]) == [0]
    red = trace.reduce(ev, [0])
    assert 0.05 < red["window_s"] < 0.07
    # five steps of ~0.18 ms of device work in a ~59 ms window; the
    # device's clock runs ~1.3 ms behind the host's here, so the first
    # step starts before the window and is cut
    assert 4 * 0.17e-3 < red["busy_s"][0] < 5 * 0.19e-3
    names = [n for n, _ in red["device_ops"]]
    assert "convolution_tanh_fusion" in names
    assert {g.split(" ")[0] for g, _ in red["idle_gaps"]} <= {
        "dispatch", "wait", "device_put", "between"}


def test_pipeline_ops_grouped_by_stage_program():
    ev = _constructed()
    ev["modules"] = {0: [("jit_fn(123)", 5 * MS, 30 * MS)],
                     1: [("jit_bwd(456)", -20 * MS, 110 * MS)]}
    ops = dict(trace.reduce(ev, [0, 1])["device_ops"])
    assert ops["jit_fn (device 0)"] == pytest.approx(0.025)   # 10-35 ms
    assert ops["convolution"] == pytest.approx(0.010)         # no program
    assert ops["jit_bwd (device 1)"] == pytest.approx(0.080)
    # one device: the instruction names, as before
    assert "fusion" in dict(trace.reduce(ev, [0])["device_ops"])
