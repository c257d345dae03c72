"""Observability layer tests (repro.obs).

Chrome-trace schema validation of the predicted + executed exports for
all four pipeline schedules, the predicted-vs-executed diff report,
span nesting / thread safety / disabled-default, metrics-registry
semantics (counter/gauge/histogram, Prometheus text), the XLA-profiler
hook's graceful fallback + trace parsing, the measurement store's
incremental readers, and the per-op-type calibration buckets.
"""
import gzip
import json
import threading

import pytest

from repro.core.device import testbed as make_testbed
from repro.core.graph import CompGraph, OpNode, group_graph
from repro.core.strategy import Action, Option, Strategy
from repro.exec import (
    build_stage_plan, execute_pipeline, make_schedule, simulate_schedule)
from repro.obs import (
    MetricsRegistry, Tracer, chrome_trace, diff_report, executed_events_of,
    executed_trace_events, format_diff, get_tracer, set_tracer,
    timeline_trace_events, validate_chrome_trace, write_chrome_trace,
    xla_profiler as xp)

SCHEDULES = ("gpipe", "1f1b", "interleaved", "zb")


def _chain_gg(n_ops: int = 12, n_groups: int = 6):
    g = CompGraph(name="chain")
    for i in range(n_ops):
        g.add_node(OpNode(i, f"op{i}", "dot_general",
                          flops=1e9 * (1 + i % 3), bytes_out=1e6,
                          param_bytes=4e5, grad_bytes=4e5,
                          is_grad_producer=True))
        if i:
            g.add_edge(i - 1, i, 1e6)
    assign = {i: i * n_groups // n_ops for i in range(n_ops)}
    return group_graph(g, assign)


def _pipe_strategy(gg, placement):
    return Strategy([
        Action(placement, Option.PIPE) if i % 2 == 0
        else Action(placement, Option.PS) for i in range(gg.n)])


def _plan(name):
    import copy
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    if name == "interleaved":           # needs n_micro % n_stages == 0
        plan = copy.deepcopy(plan)
        plan.n_micro = 2 * plan.n_stages
    return plan, topo


# ------------------------------------------------------------ trace export

@pytest.mark.parametrize("name", SCHEDULES)
def test_trace_export_schema_all_schedules(name, tmp_path):
    """Predicted + executed exports validate against the trace-event
    schema for every schedule, with both pid tracks, per-stage thread
    metadata, and one complete event per timeline event."""
    plan, topo = _plan(name)
    predicted = simulate_schedule(
        plan, topo, make_schedule(name, plan.n_stages, plan.n_micro))
    rec, _ = execute_pipeline(plan, topo, schedule=name)

    events = timeline_trace_events(predicted, pid=0) \
        + executed_trace_events(rec, pid=1, n_stages=plan.n_stages)
    path = write_chrome_trace(str(tmp_path / f"trace_{name}.json"), events,
                              schedule=name)
    with open(path) as f:
        doc = json.load(f)
    validate_chrome_trace(doc)
    assert doc["otherData"]["schedule"] == name

    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    # both sides rendered: one complete event per predicted timeline
    # event, and the executed stream mirrors it at noise 0
    assert len([e for e in xs if e["pid"] == 0]) == len(predicted.events)
    assert len([e for e in xs if e["pid"] == 1]) == \
        len(rec.meta["events"])
    assert {e["args"]["name"] for e in metas
            if e["name"] == "process_name"} == {"predicted", "executed"}
    stage_names = {e["args"]["name"] for e in metas
                   if e["name"] == "thread_name" and e["pid"] == 0}
    assert {f"stage {s}" for s in range(plan.n_stages)} <= stage_names
    # compute events on stage tracks, transfers shifted past them
    for e in xs:
        kind = e["args"]["kind"]
        if kind == "transfer":
            assert e["tid"] >= plan.n_stages
            assert e["name"].startswith("X")
        else:
            assert e["tid"] == e["args"]["stage"] < plan.n_stages
    if name == "zb":
        assert any(e["name"].startswith("W") for e in xs)
    if name == "interleaved":
        assert any("c1" in e["name"] for e in xs)


def test_trace_event_names_and_colors():
    plan, topo = _plan("1f1b")
    tl = simulate_schedule(
        plan, topo, make_schedule("1f1b", plan.n_stages, plan.n_micro))
    events = timeline_trace_events(tl)
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["cname"] for e in xs} <= {"good", "bad", "yellow", "grey"}
    f0 = next(e for e in xs if e["args"]["kind"] == "forward"
              and e["args"]["stage"] == 0 and e["args"]["mb"] == 0)
    assert f0["name"] == "F0.0" and f0["cname"] == "good"
    assert f0["ts"] >= 0 and f0["dur"] > 0
    x = next(e for e in xs if e["args"]["kind"] == "transfer")
    assert "->" in x["name"] and x["args"]["nbytes"] > 0


def test_executed_events_of_normalizes_all_shapes():
    dicts = [{"kind": "F", "stage": 0, "mb": 1, "start": 0.5,
              "finish": 0.75}]
    norm = executed_events_of(dicts)
    assert norm == [{"kind": "F", "stage": 0, "mb": 1, "chunk": 0,
                     "src": -1, "start": 0.5, "finish": 0.75}]

    class FakeStats:                    # engine StepStats 6-tuples
        events = [("B", 2, 3, 0.25, 1, 1.0)]
    norm = executed_events_of(FakeStats())
    assert norm[0] == {"kind": "B", "stage": 2, "mb": 3, "chunk": 1,
                       "src": -1, "start": 1.0, "finish": 1.25}

    class FakeRecord:                   # StepRecord meta["events"]
        meta = {"events": dicts}
    assert executed_events_of(FakeRecord()) == executed_events_of(dicts)


def test_write_chrome_trace_gzip_and_validation(tmp_path):
    events = [{"name": "a", "ph": "X", "ts": 1.0, "dur": 2.0,
               "pid": 0, "tid": 0}]
    path = write_chrome_trace(str(tmp_path / "t.json.gz"), events)
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "a"

    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})          # no traceEvents
    with pytest.raises(ValueError):
        validate_chrome_trace(chrome_trace(
            [{"name": "a", "ph": "X", "pid": 0, "tid": 0}]))   # no ts
    with pytest.raises(ValueError):
        validate_chrome_trace(chrome_trace(
            [{"name": "a", "ph": "X", "ts": 0.0, "dur": -1.0,
              "pid": 0, "tid": 0}]))                   # negative dur
    with pytest.raises(ValueError):
        validate_chrome_trace(chrome_trace([{"ph": "X", "ts": 0.0}]))
    # metadata events need no ts
    validate_chrome_trace(chrome_trace(
        [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
          "args": {"name": "p"}}]))


# ------------------------------------------------------------- diff report

def test_diff_report_exact_match_at_zero_noise():
    plan, topo = _plan("1f1b")
    predicted = simulate_schedule(
        plan, topo, make_schedule("1f1b", plan.n_stages, plan.n_micro))
    rec, _ = execute_pipeline(plan, topo, schedule="1f1b")
    rep = diff_report(predicted, rec, executed_wall=rec.wall_time)
    assert rep["events_matched"] == rep["events_predicted"] \
        == rep["events_executed"] == len(predicted.events)
    assert rep["unmatched"] == []
    assert abs(rep["attribution"]["compute_s"]) < 1e-9
    assert abs(rep["attribution"]["transfer_s"]) < 1e-9
    # replay wall time includes the post-flush gradient sync the bare
    # timeline does not predict -> lands in sync/other
    assert rep["step_error_s"] == pytest.approx(
        rep["attribution"]["sync_other_s"], abs=1e-9)
    assert rep["step_error_s"] >= 0
    txt = format_diff(rep)
    assert "attribution:" in txt and "matched" in txt


def test_diff_report_attributes_noise():
    plan, topo = _plan("1f1b")
    predicted = simulate_schedule(
        plan, topo, make_schedule("1f1b", plan.n_stages, plan.n_micro))
    rec, _ = execute_pipeline(plan, topo, schedule="1f1b", noise=0.3,
                              seed=7)
    rep = diff_report(predicted, rec, executed_wall=rec.wall_time)
    assert rep["events_matched"] == len(predicted.events)
    a = rep["attribution"]
    assert abs(a["compute_s"]) > 0 and abs(a["transfer_s"]) > 0
    assert rep["step_error_s"] == pytest.approx(
        a["compute_s"] + a["transfer_s"] + a["sync_other_s"])
    assert rep["worst_events"]
    assert abs(rep["worst_events"][0]["delta_s"]) >= \
        abs(rep["worst_events"][-1]["delta_s"])
    by_kind = rep["by_kind"]
    assert set(by_kind) >= {"F", "B", "X"}
    for agg in by_kind.values():
        assert agg["delta_s"] == pytest.approx(
            agg["executed_s"] - agg["predicted_s"])


def test_diff_report_flags_unmatched_events():
    plan, topo = _plan("1f1b")
    predicted = simulate_schedule(
        plan, topo, make_schedule("1f1b", plan.n_stages, plan.n_micro))
    executed = [{"kind": "F", "stage": 0, "mb": 99, "start": 0.0,
                 "finish": 1.0}]
    rep = diff_report(predicted, executed)
    assert rep["events_matched"] == 0
    assert len(rep["unmatched"]) == len(predicted.events) + 1


# ------------------------------------------------------------------ spans

def test_tracer_disabled_by_default_and_noop():
    tr = Tracer()
    assert not tr.enabled
    ctx = tr.span("x")
    assert ctx is tr.span("y")          # shared no-op context manager
    with ctx:
        pass
    assert len(tr) == 0


def test_tracer_nesting_and_summary():
    tr = Tracer(enabled=True)
    with tr.span("plan", cat="planner", model="m"):
        with tr.span("search", cat="planner"):
            with tr.span("playout", cat="mcts", iter=0):
                pass
        with tr.span("store_put", cat="planner"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["plan"].depth == 0
    assert spans["search"].depth == 1 and spans["store_put"].depth == 1
    assert spans["playout"].depth == 2
    assert spans["playout"].args == {"iter": 0}
    # children finish before (and inside) their parent
    assert spans["plan"].start <= spans["playout"].start
    assert spans["playout"].end <= spans["plan"].end
    assert spans["plan"].dur >= 0
    summ = tr.summary()
    assert summ["planner/plan"]["count"] == 1
    assert summ["mcts/playout"]["total_s"] >= 0
    tr.clear()
    assert len(tr) == 0


def test_tracer_thread_safety():
    tr = Tracer(enabled=True)
    gate = threading.Barrier(4)         # all threads alive concurrently
    # (thread idents — and so tids — can be reused otherwise)

    def worker(k):
        gate.wait()
        for i in range(50):
            with tr.span("outer", cat="t", k=k):
                with tr.span("inner", cat="t"):
                    pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 4 * 50 * 2
    assert {s.tid for s in spans} == set(range(4))   # dense per-thread ids
    for s in spans:                     # nesting is per thread
        assert s.depth == (0 if s.name == "outer" else 1)


def test_tracer_max_spans_drops():
    tr = Tracer(enabled=True, max_spans=3)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert len(tr) == 3 and tr.dropped == 2


def test_tracer_to_chrome_roundtrip():
    tr = Tracer(enabled=True)
    with tr.span("plan", cat="planner"):
        with tr.span("search", cat="planner"):
            pass
    events = tr.to_chrome(process_name="test")
    doc = validate_chrome_trace(chrome_trace(events))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"plan", "search"}
    assert all(e["args"]["depth"] in (0, 1) for e in xs)
    assert any(e["name"] == "process_name" and e["args"]["name"] == "test"
               for e in doc["traceEvents"])


def test_global_tracer_swap():
    assert not get_tracer().enabled     # instrumentation is opt-in
    tr = Tracer(enabled=True)
    old = set_tracer(tr)
    try:
        assert get_tracer() is tr
    finally:
        assert set_tracer(old) is tr
    assert get_tracer() is old


# ---------------------------------------------------------------- metrics

def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "reqs")
    c.inc(source="hit")
    c.inc(2.0, source="hit")
    c.inc(source="cold")
    assert c.value(source="hit") == 3.0
    assert c.value(source="cold") == 1.0
    assert c.value(source="nope") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    assert reg.counter("requests_total") is c    # get-or-create


def test_gauge_semantics():
    g = MetricsRegistry().gauge("store_size")
    g.set(5)
    g.set(3)
    assert g.value() == 3.0
    g.inc()
    assert g.value() == 4.0
    g.set(0.5, shard="a")
    assert g.value(shard="a") == 0.5 and g.value() == 4.0


def test_histogram_buckets_and_snapshot():
    h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["sum"] == pytest.approx(56.05)
    assert snap["min"] == 0.05 and snap["max"] == 50.0
    # cumulative per-bucket counts, +Inf catches everything
    assert snap["buckets"] == {"0.1": 1, "1.0": 3, "10.0": 4, "+Inf": 5}
    assert h.snapshot(other="label") == {"count": 0, "sum": 0.0}


def test_registry_kind_conflict_and_dumps():
    reg = MetricsRegistry()
    reg.counter("x", "a counter").inc()
    with pytest.raises(ValueError):
        reg.gauge("x")
    reg.gauge("g").set(1.5, role="planner")
    reg.histogram("h", buckets=(1.0,)).observe(0.5)

    d = reg.to_dict()
    assert d["x"]["kind"] == "counter" and d["x"]["series"][""] == 1.0
    assert d["g"]["series"]['{role="planner"}'] == 1.5
    assert d["h"]["series"][""]["count"] == 1
    json.dumps(d)                       # JSON-able end to end

    text = reg.to_prometheus()
    assert "# TYPE x counter" in text and "# HELP x a counter" in text
    assert 'g{role="planner"} 1.5' in text
    assert 'h_bucket{le="1.0"} 1' in text
    assert 'h_bucket{le="+Inf"} 1' in text
    assert "h_sum 0.5" in text and "h_count 1" in text


# ----------------------------------------------- planner spans + metrics

def test_planner_emits_spans_and_metrics():
    from repro.service.planner import PlannerService
    gg = _chain_gg()
    topo = make_testbed()
    svc = PlannerService(use_registry=False, warm_start=False)
    old = set_tracer(Tracer(enabled=True))
    try:
        svc.plan_graph(gg, topo, iterations=3)       # cold
        svc.plan_graph(gg, topo, iterations=3)       # hit
        names = {(s.cat, s.name) for s in get_tracer().spans()}
    finally:
        set_tracer(old)
    for want in (("planner", "plan"), ("planner", "fingerprint"),
                 ("planner", "store_lookup"), ("planner", "search"),
                 ("mcts", "playout"), ("mcts", "evaluate"),
                 ("mcts", "simulate")):
        assert want in names, want

    m = svc.stats()["metrics"]
    req = m["planner_requests_total"]["series"]
    assert req['{source="cold"}'] == 1.0
    assert req['{source="hit"}'] == 1.0
    lat = m["planner_plan_seconds"]["series"]
    assert lat['{source="cold"}']["count"] == 1
    assert m["planner_playouts"]["series"]['{source="cold"}'][
        "count"] == 1
    assert m["planner_store_size"]["series"][""] >= 1.0
    assert "planner_requests_total" in svc.metrics.to_prometheus()


# ------------------------------------------------------- xla profiler hook

def test_classify_op():
    assert xp.classify_op("all-reduce.3") == "allreduce"
    assert xp.classify_op("AllReduceStart") == "allreduce"
    assert xp.classify_op("reduce-scatter.1") == "allreduce"
    assert xp.classify_op("all-gather.7") == "allreduce"
    assert xp.classify_op("collective-permute.2") == "xfer"
    assert xp.classify_op("copy-start.1") == "xfer"
    assert xp.classify_op("dot_general.5") is None
    assert xp.classify_op("fusion.12") is None


def test_parse_trace_collectives(tmp_path):
    doc = {"traceEvents": [
        {"name": "all-reduce.1", "ph": "X", "ts": 0, "dur": 2000.0,
         "pid": 0, "tid": 0, "args": {"bytes_accessed": 4096}},
        {"name": "collective-permute.9", "ph": "X", "ts": 10, "dur": 500.0,
         "pid": 0, "tid": 0, "args": {}},
        {"name": "dot_general.2", "ph": "X", "ts": 20, "dur": 9000.0,
         "pid": 0, "tid": 0},
        {"name": "all-reduce.zero", "ph": "X", "ts": 30, "dur": 0.0,
         "pid": 0, "tid": 0},
        {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0},
    ]}
    path = tmp_path / "perfetto_trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    samples = xp.parse_trace_collectives(
        str(path), nominal_bw=1e9, n_dev=4, link="cross", pair="0-1")
    assert len(samples) == 2            # non-collective + zero-dur skipped
    ar = samples[0]
    assert ar["kind"] == "allreduce" and ar["nbytes"] == 4096.0
    assert ar["time"] == pytest.approx(2e-3)     # dur is microseconds
    assert ar["n_dev"] == 4 and ar["link"] == "cross"
    assert ar["pair"] == "0-1" and ar["nominal_bw"] == 1e9
    assert samples[1]["kind"] == "xfer" and samples[1]["nbytes"] == 0.0


def test_profile_step_unavailable_fallback(monkeypatch, tmp_path):
    monkeypatch.setattr(xp, "profiler_available", lambda: False)
    out, samples, meta = xp.profile_step(
        lambda a, b: a + b, 2, 3, log_dir=str(tmp_path))
    assert out == 5 and samples == []
    assert meta == {"profiler": "unavailable"}


def test_profile_step_no_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(xp, "find_trace_files", lambda d: [])

    class FakeCtx:
        def __init__(self, *a, **k):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    import jax
    monkeypatch.setattr(jax.profiler, "trace", FakeCtx)
    out, samples, meta = xp.profile_step(lambda: 7, log_dir=str(tmp_path))
    assert out == 7 and samples == []
    assert meta["profiler"] == "no_trace"


def test_profile_step_trace_failure_reraises_without_rerun(monkeypatch,
                                                          tmp_path):
    class FailingCtx:
        def __init__(self, *a, **k):
            pass

        def __enter__(self):
            raise RuntimeError("profiler refused")

        def __exit__(self, *exc):
            return False

    import jax
    monkeypatch.setattr(jax.profiler, "trace", FailingCtx)
    calls = []
    with pytest.raises(RuntimeError, match="profiler refused"):
        xp.profile_step(lambda: calls.append(1), log_dir=str(tmp_path))
    assert calls == []


def test_profile_step_missing_profile_raises_on_tpu(monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(xp, "profiler_available", lambda: False)
    with pytest.raises(RuntimeError, match="unavailable"):
        xp.profile_step(lambda: 7, log_dir=str(tmp_path))


def test_attach_collectives():
    from repro.runtime.telemetry import StepRecord
    rec = StepRecord(collectives=[{"kind": "xfer"}])
    out = xp.attach_collectives(
        rec, [{"kind": "allreduce"}], {"profiler": "ok"})
    assert out is rec and len(rec.collectives) == 2
    assert rec.meta["xla_profiler"]["profiler"] == "ok"


# --------------------------------------------- measurement store readers

def _rec(step, fp="g"):
    from repro.runtime.telemetry import StepRecord
    return StepRecord(graph_fp=fp, topo_fp="t", step=step,
                      wall_time=0.1 * (step + 1))


def test_store_tail_reads_newest_first_ordered(tmp_path):
    from repro.runtime.telemetry import MeasurementStore
    store = MeasurementStore(str(tmp_path))
    for i in range(20):
        store.append(_rec(i, fp="g" if i % 2 == 0 else "other"))
    out = store.tail(3)
    assert [r.step for r in out] == [17, 18, 19]     # oldest first
    out = store.tail(3, graph_fp="g")                # filtered tail
    assert [r.step for r in out] == [14, 16, 18]
    # tiny blocks force the backwards multi-block path
    out = store.tail(5, block_size=64)
    assert [r.step for r in out] == list(range(15, 20))
    assert store.records(limit=3)[-1].step == 19     # delegates to tail
    assert store.tail(0) == []


def test_store_read_new_incremental(tmp_path):
    from repro.runtime.telemetry import MeasurementStore
    store = MeasurementStore(str(tmp_path))
    for i in range(3):
        store.append(_rec(i))
    assert [r.step for r in store.read_new()] == [0, 1, 2]
    assert store.read_new() == []                    # cursor advanced
    store.append(_rec(3))
    store.append(_rec(4, fp="other"))
    assert [r.step for r in store.read_new(graph_fp="g")] == [3]
    assert store.read_new() == []


def test_store_read_new_torn_line_and_truncation(tmp_path):
    from repro.runtime.telemetry import MeasurementStore
    store = MeasurementStore(str(tmp_path))
    store.append(_rec(0))
    assert len(store.read_new()) == 1
    # a torn in-flight append stays buffered until its newline lands
    with open(store.path, "a") as f:
        f.write('{"graph_fp": "g", "step": 1')
    assert store.read_new() == []
    with open(store.path, "a") as f:
        f.write(', "wall_time": 0.5}\n')
    assert [r.step for r in store.read_new()] == [1]
    # rotation/truncation resets the cursor and replays from the start
    with open(store.path, "w") as f:
        f.write("")
    store.append(_rec(9))
    assert [r.step for r in store.read_new()] == [9]


def test_store_memory_mode_readers():
    from repro.runtime.telemetry import MeasurementStore
    store = MeasurementStore()
    for i in range(5):
        store.append(_rec(i))
    assert [r.step for r in store.tail(2)] == [3, 4]
    assert len(store.read_new()) == 5
    assert store.read_new() == []
    store.append(_rec(5))
    assert [r.step for r in store.read_new()] == [5]


# ------------------------------------------- per-op calibration buckets

def test_fit_profile_per_op_buckets():
    from repro.runtime.calibration import fit_profile, profile_metrics
    from repro.runtime.telemetry import StepRecord
    from repro.core.device import peak_flops
    topo = make_testbed()
    peak = peak_flops("V100")
    records = []
    for k in range(4):
        records.append(StepRecord(compute=[
            # forward runs at 50% utilization, backward at 25%
            {"gpu_type": "V100", "op": "F", "flops": 1e12,
             "time": 1e12 / (0.5 * peak)},
            {"gpu_type": "V100", "op": "B", "flops": 2e12,
             "time": 2e12 / (0.25 * peak)},
            {"gpu_type": "V100", "kind": "W", "flops": 1e12,
             "time": 1e12 / (0.5 * peak)},        # falls back to "kind"
            {"gpu_type": "NOPE", "op": "F", "flops": 1e12, "time": 1.0},
        ]))
    prof = fit_profile(records, topo)
    assert set(prof.util_by_op) == {"V100/F", "V100/B", "V100/W"}
    assert prof.util_by_op["V100/F"] == pytest.approx(0.5, rel=1e-3)
    assert prof.util_by_op["V100/B"] == pytest.approx(0.25, rel=1e-3)
    assert prof.meta["op_samples"]["V100/F"] == 4
    # pooled per-device fit still present and between the two buckets
    assert 0.25 < prof.util["V100"] < 0.5

    # roundtrip keeps the buckets
    from repro.runtime.calibration import CalibrationProfile
    prof2 = CalibrationProfile.from_dict(prof.to_dict())
    assert prof2.util_by_op == prof.util_by_op

    reg = profile_metrics(prof)
    d = reg.to_dict()
    by_op = d["calibration_utilization_by_op"]["series"]
    assert by_op['{gpu_type="V100",op="F"}'] == pytest.approx(
        0.5, rel=1e-3)
    assert d["calibration_records"]["series"][""] == 4.0
    assert "calibration_utilization_by_op" in reg.to_prometheus()


def test_replay_samples_feed_op_buckets():
    """End-to-end: replay-executed pipeline telemetry carries per-event
    kinds that land in the per-op utilization tier."""
    from repro.runtime.calibration import fit_profile
    from repro.runtime.telemetry import MeasurementStore
    plan, topo = _plan("zb")
    store = MeasurementStore()
    for step in range(3):
        execute_pipeline(plan, topo, schedule="zb", step=step, store=store)
    prof = fit_profile(store.records(), topo)
    ops = {k.split("/", 1)[1] for k in prof.util_by_op}
    assert {"F", "B", "W"} <= ops


# ----------------------------------------------------------- CLI metrics

def test_cli_metrics_smoke(tmp_path, capsys):
    from repro.service.cli import main
    rc = main(["metrics", "--cache-dir", str(tmp_path / "plans"),
               "--format", "json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    m = out["stats"]["metrics"]
    assert m["planner_store_size"]["series"][""] == 0.0
    rc = main(["metrics", "--cache-dir", str(tmp_path / "plans")])
    assert rc == 0
    assert "planner_store_size 0" in capsys.readouterr().out
