"""A whole run through the harness at a CPU size: the window loop, the
check against the reference and the result line's shape."""
import json

from bench_tiny import run_tiny, tiny

CELL = "qwen2-1.5b-L8.b2s4k"


def test_run_line_shape_and_correct(compile_cache):
    line, err = run_tiny(tiny(CELL))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["count"] == 1
    assert line["window_compiles"] == 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "change_gap",
                                   "window_nonfinite_losses"}
    # each number compared is printed beside its limit, last on stderr
    last = err.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") and "limit" in s for s in last)
    json.dumps(line, allow_nan=False)


def test_traced_run_reports_per_layer_shape(compile_cache):
    line, _ = run_tiny(tiny(CELL), trace=True)
    assert line["correct"] is True
    # no device trace on the CPU: the readers find nothing and say so
    assert line["metrics"] == {}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
