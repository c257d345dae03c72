"""The program's spans and scopes in the trace: idle split into starved
and waiting, gaps named by the innermost span, time by scope, and the
readers of the three metrics built on them."""
from pathlib import Path

import pytest

from bench import harness, spans, trace
from bench.metrics import attention_share, host_syncs_per_step, starved_idle_share

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _constructed():
    # window 0..100 ms. Device 0 runs stage_fwd 10-30 and stage_bwd 50-70;
    # the host dispatches them at 9 and 45 ms, then reads the loss 75-90.
    # Device 1 runs one eager add, 20-25 ms, and nothing the engine sent.
    return {
        "host": [("bench_window", 0, 100 * MS), ("dispatch", 0, 95 * MS),
                 ("wait", 95 * MS, 5 * MS)],
        "spans": [
            ("pipeline.step", 1 * MS, 93 * MS, {"step": 1}),
            ("pipeline.F", 4 * MS, 5 * MS,
             {"program": "stage_fwd", "devices": 0, "stage": 0, "mb": 0}),
            ("pipeline.transfer", 4 * MS, 2 * MS, {"what": "mb"}),
            ("pipeline.B", 38 * MS, 7 * MS,
             {"program": "stage_bwd", "devices": "0", "stage": 0, "mb": 0}),
            ("pipeline.sync", 75 * MS, 15 * MS, {"what": "loss"}),
        ],
        "modules": {0: [("jit_stage_fwd(11)", 10 * MS, 20 * MS),
                        ("jit_stage_bwd(12)", 50 * MS, 20 * MS)],
                    1: [("jit_add(13)", 20 * MS, 5 * MS)]},
        "devices": {0: [("%fusion.1 = x", 10 * MS, 20 * MS),
                        ("%fusion.2 = x", 50 * MS, 20 * MS)],
                    1: [("%add.3 = x", 20 * MS, 5 * MS)]},
    }


def test_idle_splits_into_starved_and_waiting():
    red = spans.reduce(_constructed(), [0, 1])
    # device 0 idles 60 ms: 0-10 (nothing sent until 9), 30-50 (stage_bwd
    # sent at 45, so 5 ms waiting), 70-100 (nothing sent)
    assert red["starved_s"][0] == pytest.approx(0.054)
    assert red["starved_s"][1] == pytest.approx(0.095)
    assert red["eager_s"] == {0: 0.0, 1: pytest.approx(0.005)}
    assert red["programs"] == ["stage_bwd", "stage_fwd"]
    assert red["queue"][0][1] == [1, 0, 1, 0]
    assert (red["steps"], red["syncs"]) == (1, 1)
    assert red["idle_by_span"] == {
        "pipeline.step": [pytest.approx(0.095), pytest.approx(0.095)],
        "pipeline.sync[loss]": [pytest.approx(0.030), pytest.approx(0.030)],
        "pipeline.B": [pytest.approx(0.020), pytest.approx(0.015)],
        "pipeline.transfer[mb]": [pytest.approx(0.010), pytest.approx(0.009)]}


def test_gaps_named_by_the_innermost_span():
    ev = _constructed()
    gaps = spans.reduce(ev, [0, 1])["idle_gaps"]
    assert gaps[0] == ("pipeline.step (device 1)", pytest.approx(0.075))
    assert ("pipeline.sync[loss] (device 0)", pytest.approx(0.030)) in gaps
    assert ("pipeline.B (device 0)", pytest.approx(0.020)) in gaps
    assert ("pipeline.transfer[mb] (device 0)", pytest.approx(0.010)) in gaps
    # the harness's own reduction names the same gaps by its spans only
    assert ("dispatch (device 0)", pytest.approx(0.030)) in \
        trace.reduce(ev, [0, 1])["idle_gaps"]


@pytest.mark.parametrize("ev,devices", [
    (_constructed(), [0, 1]),
    (spans.events(str(DATA / "v5e_matmul.xplane.pb")), [0]),
])
def test_busy_and_window_as_the_harness_reads_them(ev, devices):
    ours, theirs = spans.reduce(ev, devices), trace.reduce(ev, devices)
    assert ours["window_s"] == theirs["window_s"]
    assert ours["busy_s"] == theirs["busy_s"]


def test_recorded_chip_trace_has_no_program_spans():
    ev = spans.events(str(DATA / "v5e_matmul.xplane.pb"))
    assert ev["spans"] == []
    red = spans.reduce(ev, [0])
    assert red["starved_s"] == {} and red["steps"] == 0
    assert {g.split(" ")[0] for g, _ in red["idle_gaps"]} <= {
        "dispatch", "wait", "device_put", "between"}


def test_time_by_scope():
    red = spans.reduce(_constructed(), [0, 1],
                       op_scopes={"fusion.1": "attention", "fusion.2": "mlp"})
    assert red["scope_s"] == {"attention": pytest.approx(0.020),
                              "mlp": pytest.approx(0.020),
                              "unscoped": pytest.approx(0.005)}
    assert red["program_s"] == {"jit_stage_fwd": pytest.approx(0.020),
                                "jit_stage_bwd": pytest.approx(0.020),
                                "jit_add": pytest.approx(0.005)}


def test_scopes_of_takes_the_outermost_known_scope():
    text = "\n".join(
        f'%{ins} = f32[2] fusion(%a), metadata={{op_name="{op}"}}' for ins, op in [
            ("fusion.1", "jit(train_step)/transpose(jvp(loss))/while/body/"
                         "attention/dot_general"),
            ("fusion.2", "jit(train_step)/optimizer/mul"),
            ("fusion.3", "jit(train_step)/while/body/dynamic_slice"),
            ("fusion.4", "jit(train_step)/transpose(jvp(head_ce))/mul")])
    assert spans.scopes_of(text) == {"fusion.1": "attention",
                                     "fusion.2": "optimizer",
                                     "fusion.4": "head_ce"}


class _Dev:
    def __init__(self, i):
        self.id = i


def _with_trace(monkeypatch, tmp_path, ev):
    path = str(tmp_path / "t.xplane.pb")
    monkeypatch.setattr(spans, "locate", lambda: path)
    monkeypatch.setattr(spans, "events", lambda p: ev)
    spans._reduced.cache_clear()
    return {"devices": [_Dev(0), _Dev(1)], "cell": None, "busy_s": [0.04, 0.005]}


def test_readers_on_a_trace_with_program_spans(monkeypatch, tmp_path):
    ctx = _with_trace(monkeypatch, tmp_path, _constructed())
    assert starved_idle_share.read(ctx) == pytest.approx(
        100 * (0.054 + 0.095) / 2 / 0.1)
    assert host_syncs_per_step.read(ctx) == 1.0
    text = '%fusion.1 = f32[2] fusion(), metadata={op_name="jit(f)/attention/dot"}'
    monkeypatch.setattr(spans, "step_hlo", lambda cell, devices: text)
    assert attention_share.read(ctx) == pytest.approx(100 * 0.020 / 0.045)


@pytest.mark.parametrize("reader", [starved_idle_share, host_syncs_per_step,
                                    attention_share])
def test_readers_read_nothing_without_the_programs_spans(
        monkeypatch, tmp_path, reader):
    # a program older than its spans and scopes: no value, and no error
    ev = {k: v for k, v in _constructed().items() if k != "spans"}
    ctx = _with_trace(monkeypatch, tmp_path, ev)
    monkeypatch.setattr(spans, "step_hlo", lambda cell, devices: "")
    assert reader.read(ctx) is None


@pytest.mark.parametrize("reader", [starved_idle_share, host_syncs_per_step,
                                    attention_share])
def test_readers_read_nothing_without_a_trace(monkeypatch, tmp_path, reader):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    assert reader.read({"devices": [_Dev(0)], "cell": None, "busy_s": [0.1]}) is None


def test_locate_finds_the_newest_trace(monkeypatch, tmp_path):
    import os
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    for i, name in enumerate(("bench_trace_a", "bench_trace_b", "bench_trace_c")):
        d = tmp_path / name / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        if name != "bench_trace_c":          # the newest holds no trace yet
            (d / "h.xplane.pb").write_bytes(b"")
        os.utime(tmp_path / name, (i, i))
    assert spans.locate().endswith("bench_trace_b/plugins/profile/x/h.xplane.pb")


@pytest.mark.parametrize("cell,metrics", [
    ("qwen2-1.5b-L8.b2s4k", ["mfu", "device_idle_share", "attention_share"]),
    ("qwen2-1.5b.pp4.b8s2k", ["mfu", "device_idle_share", "stage_busy_spread",
                              "starved_idle_share", "host_syncs_per_step"]),
])
def test_new_metrics_only_in_their_cells(cell, metrics):
    assert [m["name"] for m in harness.load_cell(cell).per_layer] == metrics


def test_scopes_in_the_one_chip_step():
    """The program's named scopes reach the compiled step's metadata, as
    ``step_hlo`` compiles it for the one-chip cell (CPU-sized)."""
    import jax
    from bench_tiny import tiny
    cell = tiny("qwen2-1.5b-L8.b2s4k")
    text = spans.step_hlo(cell, jax.devices()[:1])
    found = set(spans.scopes_of(text).values())
    assert {"embed", "attention", "mlp", "head_ce", "optimizer"} <= found
    # the harness's breakdown labels carry them too
    labels = trace.hlo_op_labels(text).values()
    for want in ("recompute attention/", "bwd mlp/", "fwd head_ce/",
                 "bwd embed/", "step optimizer/"):
        assert any(lab.startswith(want) for lab in labels), want
    assert spans.step_hlo(tiny("qwen2-1.5b.pp4.b8s2k"), jax.devices()[:1]) is None


def test_scopes_survive_a_cache_entry_without_them(monkeypatch, compile_cache):
    """An executable cached from the same step without its block scopes
    (the cache key leaves metadata out) still yields the scopes that ran."""
    import contextlib

    import jax
    from bench_tiny import tiny

    class NoScope(contextlib.ContextDecorator):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    cell = tiny("qwen2-1.5b-L8.b2s4k")
    harness.enable_compile_cache()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: NoScope())
        jax.clear_caches()           # traced layer bodies keep their scopes
        plain = spans.step_hlo(cell, jax.devices()[:1])
        jax.clear_caches()
    assert "attention" not in set(spans.scopes_of(plain).values())
    scoped = spans.step_hlo(cell, jax.devices()[:1])
    assert {"attention", "mlp"} <= set(spans.scopes_of(scoped).values())
