"""Pipeline execution engine tests (repro.exec).

Fast in-process coverage of the stage partitioner, the schedule event
lists and their invariants, and the predicted-vs-executed timeline
agreement; subprocess tests (forced 4-device CPU) prove loss/gradient
parity of the REAL pipelined train step against the single-device
reference across GPipe and 1F1B, and across the per-stage AR/PS/SFB
gradient-sync modes.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.device import testbed as make_testbed
from repro.core.graph import CompGraph, OpNode, group_graph
from repro.core.strategy import Action, Option, Strategy
from repro.exec import (
    build_stage_plan, execute_pipeline, flatten_schedule, make_schedule,
    max_feasible_micro, peak_stash, simulate_schedule, validate_schedule)
from repro.exec.stages import PipelineInfeasible, StagePlan, StageSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


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


def _pipe_strategy(gg, placement, sync_opt=Option.PS):
    return Strategy([
        Action(placement, Option.PIPE) if i % 2 == 0
        else Action(placement, sync_opt) for i in range(gg.n)])


# ------------------------------------------------------ stage partitioner

def test_stage_plan_cuts_at_pipe_boundaries():
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    assert plan is not None and plan.n_stages == 3
    assert plan.placement == (0, 1, 5)          # partial placement kept
    # every group on exactly one stage, contiguous topological spans
    seen = [g for s in plan.stages for g in s.op_group_ids]
    assert sorted(seen) == list(range(gg.n))
    flat = [g for s in plan.stages for g in sorted(s.op_group_ids)]
    assert flat == sorted(flat)
    # the ILP's sync decisions reach the stages (stage 1 holds only the
    # PIPE-action group, which casts no sync vote -> allreduce default)
    assert [s.sync for s in plan.stages] == ["ps", "allreduce", "ps"]
    assert [s.gpu_type for s in plan.stages] == ["V100", "1080Ti", "P100"]
    # V100 group (4 fast GPUs) gets the largest flops share
    assert plan.stages[0].flops == max(s.flops for s in plan.stages)


def test_stage_plan_none_without_multi_group_pipe():
    gg = _chain_gg()
    topo = make_testbed()
    dp = Strategy([Action((0, 1), Option.AR)] * gg.n)
    assert build_stage_plan(gg, dp, topo) is None
    single = Strategy([Action((0,), Option.PIPE)] * gg.n)
    assert build_stage_plan(gg, single, topo) is None
    assert not dp.has_pipeline() and not single.has_pipeline()


def test_stage_plan_device_assignment_infeasible():
    gg = _chain_gg()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), make_testbed())
    sets = plan.assign_local_devices(list(range(8)))
    assert len(sets) == 3 and sum(len(s) for s in sets) == 8
    assert all(len(s) >= 1 for s in sets)
    with pytest.raises(PipelineInfeasible):
        plan.assign_local_devices([0, 1])       # 2 devices < 3 stages


def test_stage_plan_roundtrip():
    gg = _chain_gg()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1)), make_testbed())
    plan2 = StagePlan.from_dict(plan.to_dict())
    assert plan2.placement == plan.placement
    assert [s.to_dict() for s in plan2.stages] == \
        [s.to_dict() for s in plan.stages]


# ------------------------------------------------------------- schedules

@pytest.mark.parametrize("name", ["gpipe", "1f1b"])
@pytest.mark.parametrize("S,M", [(2, 4), (3, 5), (4, 2), (4, 8)])
def test_schedules_validate(name, S, M):
    order = make_schedule(name, S, M)
    validate_schedule(order, S, M)
    flat = flatten_schedule(order, S, M)
    assert len(flat) == 2 * S * M


@pytest.mark.parametrize("S,M,V", [(2, 2, 2), (2, 4, 2), (3, 6, 2),
                                   (4, 8, 2), (4, 8, 3), (6, 12, 2)])
def test_interleaved_validates(S, M, V):
    order = make_schedule("interleaved", S, M, n_chunks=V)
    validate_schedule(order, S, M)
    flat = flatten_schedule(order, S, M)
    assert len(flat) == 2 * S * M * V          # F+B per virtual microbatch
    # every stage hosts every chunk
    for evs in order:
        assert {e.chunk for e in evs} == set(range(V))


@pytest.mark.parametrize("S,M", [(2, 4), (3, 5), (4, 2), (4, 8), (6, 12)])
def test_zb_validates(S, M):
    order = make_schedule("zb", S, M)
    validate_schedule(order, S, M)
    flat = flatten_schedule(order, S, M)
    assert len(flat) == 3 * S * M              # F + B + W per microbatch


def test_interleaved_rejects_bad_micro():
    with pytest.raises(ValueError):
        make_schedule("interleaved", 4, 6)     # 6 % 4 != 0
    with pytest.raises(ValueError):
        make_schedule("interleaved", 2, 4, n_chunks=1)


def test_interleaved_chunk_ordering():
    """Forwards walk chunks 0..V-1 in microbatch groups of S; backwards
    walk them V-1..0 (the Megatron issue order)."""
    S, M, V = 3, 6, 2
    order = make_schedule("interleaved", S, M, n_chunks=V)
    for evs in order:
        fwd_chunks = [e.chunk for e in evs if e.kind == "F"]
        bwd_chunks = [e.chunk for e in evs if e.kind == "B"]
        # per microbatch group of S, the chunk id is constant and cycles
        groups_f = [fwd_chunks[i:i + S] for i in range(0, len(fwd_chunks), S)]
        assert all(len(set(g)) == 1 for g in groups_f)
        assert [g[0] for g in groups_f][:V] == list(range(V))
        groups_b = [bwd_chunks[i:i + S] for i in range(0, len(bwd_chunks), S)]
        assert all(len(set(g)) == 1 for g in groups_b)
        assert [g[0] for g in groups_b][:V] == list(range(V - 1, -1, -1))


def test_zb_w_after_b_and_stash():
    """W-after-B invariant, and zero-bubble keeps exactly 1F1B's
    activation stash (W releases the stash before the next F acquires)."""
    S, M = 4, 8
    order = make_schedule("zb", S, M)
    for evs in order:
        done_b = set()
        for e in evs:
            if e.kind == "B":
                done_b.add(e.mb)
            elif e.kind == "W":
                assert e.mb in done_b
    assert peak_stash(order) == peak_stash(make_schedule("1f1b", S, M))
    # a W issued before its B must be rejected
    from repro.exec.schedule import Event
    bad = [[Event("F", 0, 0), Event("W", 0, 0), Event("B", 0, 0)]]
    with pytest.raises(ValueError):
        validate_schedule(bad, 1, 1)


def test_schedule_stash_bounds():
    S, M = 4, 8
    assert peak_stash(make_schedule("gpipe", S, M)) == [M] * S
    assert peak_stash(make_schedule("1f1b", S, M)) == \
        [min(S - s, M) for s in range(S)]


def test_memory_capped_microbatching_favors_1f1b():
    """GPipe stashes every microbatch; under a fixed per-stage activation
    budget 1F1B sustains strictly deeper microbatching."""
    gg = _chain_gg()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), make_testbed())
    kw = dict(mb_act_bytes=1e6, mem_budget=6e6)
    m_gpipe = max_feasible_micro(plan, "gpipe", **kw)
    m_1f1b = max_feasible_micro(plan, "1f1b", **kw)
    assert m_gpipe == 6
    assert m_1f1b > m_gpipe


def test_timeline_respects_dependencies():
    """No stage executes a microbatch before its predecessor produced it
    (and backwards mirror it); per-stage execution never overlaps."""
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    for name in ("gpipe", "1f1b"):
        order = make_schedule(name, plan.n_stages, plan.n_micro)
        tl = simulate_schedule(plan, topo, order)
        for m in range(plan.n_micro):
            for s in range(1, plan.n_stages):
                assert tl.finish_of("F", s, m) > tl.finish_of("F", s - 1, m)
            for s in range(plan.n_stages - 1):
                assert tl.finish_of("B", s, m) > tl.finish_of("B", s + 1, m)
        per_stage = {}
        for e in tl.events:
            if e.kind in ("F", "B"):
                per_stage.setdefault(e.stage, []).append((e.start, e.finish))
        for evs in per_stage.values():
            evs.sort()
            for (_s0, f0), (s1, _f1) in zip(evs, evs[1:],
                                            strict=False):
                assert s1 >= f0 - 1e-12          # serial per stage
        assert 0.0 < tl.bubble_fraction() < 1.0


def _uniform_plan(S=4, M=8, out_bytes=0.0):
    """Hand-built equal-stage plan: compute-dominated when out_bytes=0."""
    return StagePlan(
        stages=[StageSpec(i, i % 3, [i], flops=4e9, param_bytes=1e5,
                          grad_bytes=1e5, out_bytes=out_bytes,
                          n_devices=1, gpu_type="V100")
                for i in range(S)],
        placement=tuple(i % 3 for i in range(S)), n_micro=M)


def test_interleaved_timeline_deps():
    """Virtual-stage dependency correctness: F(u) finishes after F(u-1)
    — including the chunk wrap from the last physical stage back to the
    first — and B(u) after B(u+1)."""
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=1e6)
    V = 2
    order = make_schedule("interleaved", plan.n_stages, 6, n_chunks=V)
    tl = simulate_schedule(plan, topo, order)
    S = plan.n_stages
    for m in range(6):
        for u in range(1, S * V):
            assert tl.finish_of("F", u % S, m, u // S) > \
                tl.finish_of("F", (u - 1) % S, m, (u - 1) // S)
        for u in range(S * V - 1):
            assert tl.finish_of("B", u % S, m, u // S) > \
                tl.finish_of("B", (u + 1) % S, m, (u + 1) // S)


def test_zb_timeline_w_after_b():
    """On the timeline, W(s, m) runs after its B(s, m), and the B chain
    is NOT delayed by downstream W's (B(s, m) only needs B(s+1, m))."""
    topo = make_testbed()
    plan = _uniform_plan(S=4, M=8)
    tl = simulate_schedule(plan, topo, make_schedule("zb", 4, 8))
    for m in range(8):
        for s in range(4):
            assert tl.finish_of("W", s, m) > tl.finish_of("B", s, m)
        for s in range(3):
            assert tl.finish_of("B", s, m) > tl.finish_of("B", s + 1, m)


def test_new_schedules_beat_1f1b_bubble_when_compute_bound():
    """The headline property: on a compute-dominated pipeline, both the
    zero-bubble split and interleaved virtual stages strictly shrink the
    warm-up/drain bubble of plain 1F1B."""
    topo = make_testbed()
    plan = _uniform_plan(S=4, M=8)
    bubbles = {}
    for name in ("1f1b", "interleaved", "zb"):
        tl = simulate_schedule(plan, topo, make_schedule(name, 4, 8))
        bubbles[name] = tl.bubble_fraction()
    assert bubbles["zb"] < bubbles["1f1b"]
    assert bubbles["interleaved"] < bubbles["1f1b"]


def test_schedule_step_cost_memory_cap():
    """schedule_step_cost: depth is memory-capped per stage; parameter
    overflow is infeasible; interleaved only offers multiples of S."""
    from repro.exec import schedule_step_cost
    topo = make_testbed()
    plan = _uniform_plan(S=4, M=8, out_bytes=8e6)
    c1 = schedule_step_cost(plan, topo, "1f1b", global_micro=8)
    assert c1 is not None and c1["n_micro"] == 8 and c1["flushes"] == 1
    # a tight per-stage budget caps the depth and charges flushes
    act = [1e6] * 4
    c2 = schedule_step_cost(plan, topo, "gpipe", global_micro=8,
                            mb_act_bytes=act, mem_budget=[3e6] * 4)
    assert c2 is not None and c2["n_micro"] == 3 and c2["flushes"] == 3
    ci = schedule_step_cost(plan, topo, "interleaved", global_micro=8,
                            mb_act_bytes=act, mem_budget=[1e12] * 4)
    assert ci is not None and ci["n_micro"] % plan.n_stages == 0
    # parameters alone overflowing the group memory -> infeasible
    big = _uniform_plan(S=4, M=8)
    for st in big.stages:
        st.param_bytes = 1e13
    assert schedule_step_cost(big, topo, "1f1b", global_micro=8) is None


def test_mcts_schedule_aware_pipe_costing():
    """Schedule-aware MCTS costs pipelined strategies with the schedule
    timeline (memoized per partition+schedule) instead of the FIFO
    task-graph model, and ranks schedule variants differently."""
    from repro.core.mcts import MCTS
    from repro.exec import schedule_step_cost
    gg = _chain_gg()
    topo = make_testbed()
    strat = _pipe_strategy(gg, (0, 1, 5))
    m = MCTS(gg, topo, schedule_aware=True)
    r, res = m._evaluate(strat)
    assert len(m._pipe_cache) == 1
    plan = build_stage_plan(gg, strat, topo, n_micro=m.pipe_global_micro)
    cost = schedule_step_cost(plan, topo, plan.schedule,
                              global_micro=m.pipe_global_micro)
    assert r == pytest.approx(m.baseline_time / cost["step_time_s"])
    assert res is not None and res.makespan == \
        pytest.approx(cost["step_time_s"])
    # memoization: same partition+schedule -> no new entry
    m._evaluate(strat)
    assert len(m._pipe_cache) == 1
    # a different schedule choice lands in a different cache entry with a
    # different reward
    strat_zb = Strategy([
        Action(a.placement, a.option, schedule="zb")
        if a.option == Option.PIPE else a for a in strat.actions])
    r_zb, _ = m._evaluate(strat_zb)
    assert len(m._pipe_cache) == 2
    assert r_zb != pytest.approx(r)
    # the FIFO ablation ignores the pipeline timeline entirely
    m_fifo = MCTS(gg, topo, schedule_aware=False)
    r_fifo, _ = m_fifo._evaluate(strat)
    assert not m_fifo._pipe_cache
    assert r_fifo != pytest.approx(r)
    # a warm-seeded search tracks its best pipelined playout separately
    # from the overall winner (the seed must use candidate placements —
    # here the full spine — for the seed playout to apply)
    spine = tuple(range(topo.m))
    seed_strat = Strategy([
        Action(spine, Option.PIPE, schedule="zb") if i % 2 == 0
        else Action(spine, Option.PS) for i in range(gg.n)])
    sr = MCTS(gg, topo, schedule_aware=True,
              prior_strategy=seed_strat).search(6)
    assert sr.best_pipelined is not None
    assert sr.best_pipelined.has_pipeline()
    assert sr.best_pipelined_reward <= sr.best_reward + 1e-12
    # legacy prior (schedule="" PIPE, as stored by pre-schedule plans):
    # normalized to 1f1b so the warm seed still applies instead of
    # silently degrading to a cold search
    legacy = Strategy([
        Action(spine, Option.PIPE) if i % 2 == 0
        else Action(spine, Option.PS) for i in range(gg.n)])
    m_legacy = MCTS(gg, topo, schedule_aware=True, prior_strategy=legacy)
    assert all(a.schedule == "1f1b" for a in
               m_legacy.prior_strategy.actions
               if a.option == Option.PIPE)
    sr2 = m_legacy.search(3)
    assert sr2.best_pipelined is not None   # seed playout applied


def test_action_schedule_serialization():
    """PIPE actions carry a schedule; legacy dicts (no schedule key)
    still load, and legacy canonical JSON is byte-identical."""
    a = Action((0, 1), Option.PIPE, schedule="zb")
    assert Action.from_dict(a.to_dict()) == a
    legacy = {"placement": [0, 1], "option": "PIPE"}
    la = Action.from_dict(legacy)
    assert la.schedule == "" and la.to_dict() == legacy
    s = Strategy([a, la])
    assert Strategy.from_dict(s.to_dict()).actions == s.actions


def test_stage_plan_votes_schedule():
    gg = _chain_gg()
    topo = make_testbed()
    acts = []
    for i in range(gg.n):
        if i % 2 == 0:
            acts.append(Action((0, 1, 5), Option.PIPE, schedule="zb"))
        else:
            acts.append(Action((0, 1, 5), Option.PS))
    plan = build_stage_plan(gg, Strategy(acts), topo)
    assert plan.schedule == "zb"
    plan2 = StagePlan.from_dict(plan.to_dict())
    assert plan2.schedule == "zb"
    # legacy strategies (no schedule on PIPE) default to 1f1b
    legacy = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    assert legacy.schedule == "1f1b"


def test_bubble_decreases_with_microbatching():
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    bubbles = []
    for m in (2, 8):
        plan.n_micro = m
        tl = simulate_schedule(plan, topo, make_schedule(
            "1f1b", plan.n_stages, m))
        bubbles.append(tl.bubble_fraction())
    assert bubbles[1] < bubbles[0]


# ------------------------------------------------- overlap-aware timeline

def _xfer_link(plan, e):
    return (plan.stages[e.src].device_group,
            plan.stages[e.stage].device_group)


def test_overlap_modes_order_makespans():
    """The three overlap models order as expected on a transfer-heavy
    pipeline: "full" (streamed double-buffered boundaries) beats "link"
    (legacy: transfers overlap compute, serialize per link) beats
    "none" (eager-faithful: transfers block the destination row)."""
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=5e8)
    order = make_schedule("gpipe", 3, 6)
    tls = {m: simulate_schedule(plan, topo, order, overlap=m)
           for m in ("none", "link", "full")}
    assert tls["full"].makespan < tls["link"].makespan
    assert tls["link"].makespan < tls["none"].makespan
    for m, tl in tls.items():
        assert tl.meta["overlap"] == m
    with pytest.raises(ValueError, match="overlap"):
        simulate_schedule(plan, topo, order, overlap="bogus")


def test_overlap_transfers_overlap_compute_on_distinct_resources():
    """Under "link"/"full" a boundary transfer may run while its
    destination stage computes something else (distinct resources);
    under "none" the destination row is occupied by the transfer."""
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=5e8)
    order = make_schedule("gpipe", 3, 6)

    def overlaps(tl):
        comp = [e for e in tl.events if e.kind != "X"]
        n = 0
        for x in (e for e in tl.events if e.kind == "X"):
            for c in comp:
                if c.stage == x.stage and c.start < x.finish - 1e-15 \
                        and x.start < c.finish - 1e-15:
                    n += 1
        return n
    assert overlaps(simulate_schedule(plan, topo, order,
                                      overlap="link")) > 0
    assert overlaps(simulate_schedule(plan, topo, order,
                                      overlap="full")) > 0
    assert overlaps(simulate_schedule(plan, topo, order,
                                      overlap="none")) == 0


def test_overlap_shared_link_still_serializes():
    """Every overlap mode keeps transfers on the SAME directed link
    serialized — streaming amortizes latency, it does not parallelize
    the wire."""
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=5e8)
    order = make_schedule("gpipe", 3, 6)
    for mode in ("none", "link", "full"):
        tl = simulate_schedule(plan, topo, order, overlap=mode)
        by_link: dict = {}
        for e in tl.events:
            if e.kind == "X":
                by_link.setdefault(_xfer_link(plan, e), []).append(e)
        assert by_link, "plan should cross device groups"
        for evs in by_link.values():
            evs.sort(key=lambda e: e.start)
            for a, b in zip(evs, evs[1:]):
                assert b.start >= a.finish - 1e-12, (mode, a, b)


def test_overlap_full_streams_latency():
    """"full" only drops the wire latency on back-to-back transfers:
    every streamed transfer is shorter than a cold one by exactly the
    topology latency, and the first transfer on each link stays cold."""
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=5e8)
    order = make_schedule("gpipe", 3, 6)
    cold = {}
    for e in simulate_schedule(plan, topo, order,
                               overlap="link").events:
        if e.kind == "X":
            cold.setdefault((_xfer_link(plan, e), e.nbytes), e.dur)
    streamed = 0
    firsts: dict = {}
    for e in sorted((e for e in simulate_schedule(
            plan, topo, order, overlap="full").events if e.kind == "X"),
            key=lambda e: e.start):
        link = _xfer_link(plan, e)
        base = cold[(link, e.nbytes)]
        if link not in firsts:
            firsts[link] = e
            assert e.dur == pytest.approx(base)
        elif e.dur < base:
            assert e.dur == pytest.approx(base - topo.latency)
            streamed += 1
    assert streamed > 0


def test_schedule_step_cost_defaults_to_full_overlap():
    """The search-facing cost model prices pipelines under the scan
    engine's streaming overlap by default; the legacy model stays
    available via overlap="link" and is never cheaper."""
    from repro.exec import schedule_step_cost
    topo = make_testbed()
    plan = _uniform_plan(S=3, M=6, out_bytes=5e8)
    c_def = schedule_step_cost(plan, topo, "gpipe", global_micro=6)
    c_full = schedule_step_cost(plan, topo, "gpipe", global_micro=6,
                                overlap="full")
    c_link = schedule_step_cost(plan, topo, "gpipe", global_micro=6,
                                overlap="link")
    assert c_def["step_time_s"] == pytest.approx(c_full["step_time_s"])
    assert c_full["step_time_s"] < c_link["step_time_s"]
    assert c_def["timeline"].meta["overlap"] == "full"


# -------------------------------------------- replay + simulator agreement

@pytest.mark.parametrize("name", ["gpipe", "1f1b", "interleaved", "zb"])
def test_replay_matches_predicted_timeline(name):
    """The plan->execution cross-check: the predicted schedule timeline
    and the replay-executed one agree event-for-event at zero noise —
    for the interleaved and zero-bubble schedules too."""
    import copy
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    if name == "interleaved":               # needs n_micro % n_stages == 0
        plan = copy.deepcopy(plan)
        plan.n_micro = 2 * plan.n_stages
    rec, executed = execute_pipeline(plan, topo, schedule=name)
    predicted = simulate_schedule(
        plan, topo, make_schedule(name, plan.n_stages, plan.n_micro))
    assert abs(executed.makespan - predicted.makespan) < 1e-12
    assert len(executed.events) == len(predicted.events)
    for a, b in zip(executed.events, predicted.events, strict=True):
        assert (a.kind, a.stage, a.mb, a.chunk) == \
            (b.kind, b.stage, b.mb, b.chunk)
        assert abs(a.start - b.start) < 1e-12
        assert abs(a.finish - b.finish) < 1e-12
    assert rec.meta["bubble_frac"] == pytest.approx(
        predicted.bubble_fraction())


def test_replay_telemetry_samples():
    from repro.runtime.telemetry import MeasurementStore
    from repro.runtime.calibration import fit_profile
    gg = _chain_gg()
    topo = make_testbed()
    plan = build_stage_plan(gg, _pipe_strategy(gg, (0, 1, 5)), topo)
    store = MeasurementStore()
    for step in range(6):
        execute_pipeline(plan, topo, schedule="1f1b", step=step,
                         store=store, graph_fp="g1", topo_fp="t1")
    recs = store.records(graph_fp="g1")
    assert len(recs) == 6
    assert all(c.get("pair") for r in recs for c in r.collectives)
    prof = fit_profile(recs, topo, min_pair_samples=4)
    assert prof.pairs, "per-pair tier should fit the boundary links"
    t2 = prof.apply(topo)
    assert t2.pair_eff                          # feeds Topology.bw()


# -------------------------------------------------- real execution parity

def test_pipeline_parity_vs_single_device():
    """A >= 2-stage strategy executes end-to-end on a CPU mesh with loss
    and gradients allclose to the single-device reference under both
    GPipe and 1F1B, with per-stage telemetry recorded."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import init_params, loss_fn
        from repro.exec import PipelineRunner, split_model
        from repro.exec.stages import StagePlan, StageSpec
        from repro.runtime.telemetry import MeasurementStore

        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ref_loss, _ = jax.jit(
            lambda p, b: loss_fn(cfg, p, b, remat=False))(params, batch)
        ref_grads = jax.jit(jax.grad(
            lambda p, b: loss_fn(cfg, p, b, remat=False)[0]))(params, batch)

        def maxerr(a, b):
            return max(float(jnp.max(jnp.abs(x - y))) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        devs = jax.devices()
        hi = cfg.num_periods // 2
        for sched in ("gpipe", "1f1b"):
            plan = StagePlan(
                stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                                  grad_bytes=0, out_bytes=1e5,
                                  n_devices=1, gpu_type="V100")
                        for i in range(2)],
                placement=(0, 1), n_micro=4)
            store = MeasurementStore()
            sp, fns, keys, tied = split_model(cfg, params, 2)
            runner = PipelineRunner(
                fns, plan, [[devs[0]], [devs[1]]], schedule=sched,
                n_micro=4, mb_keys=keys, tied_ref=tied, store=store)
            grads, stats = runner.step(runner.place_params(sp), batch,
                                       record=True)
            assert abs(stats.loss - float(ref_loss)) < 1e-4, sched
            errs = [
                maxerr(grads[0]["embed"], ref_grads["embed"]),
                maxerr(grads[0]["blocks"], jax.tree.map(
                    lambda a: a[:hi], ref_grads["blocks"])),
                maxerr(grads[1]["blocks"], jax.tree.map(
                    lambda a: a[hi:], ref_grads["blocks"])),
                maxerr(grads[1]["final_norm"], ref_grads["final_norm"]),
            ]
            assert max(errs) < 1e-4, (sched, errs)
            rec = store.records()[-1]
            assert rec.meta["schedule"] == sched
            stages = {(c["stage"], c["kind"]) for c in rec.compute}
            assert {(0, "F"), (0, "B"), (1, "F"), (1, "B")} <= stages
            # GPipe stashes every microbatch; 1F1B drains as it goes
            assert stats.peak_stash == (8 if sched == "gpipe" else 3)
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


def test_pipeline_stage_dp_sync_modes():
    """Per-stage data parallelism: each stage spans 2 devices and syncs
    its parameter gradients via AR / PS / SFB — all allclose to the
    single-device reference (the §4.2.3 decisions on the real engine)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import init_params, loss_fn
        from repro.exec import PipelineRunner, split_model
        from repro.exec.stages import StagePlan, StageSpec

        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ref_grads = jax.jit(jax.grad(
            lambda p, b: loss_fn(cfg, p, b, remat=False)[0]))(params, batch)

        def maxerr(a, b):
            return max(float(jnp.max(jnp.abs(x - y))) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        devs = jax.devices()
        hi = cfg.num_periods // 2
        for sync in ("allreduce", "ps", "sfb"):
            plan = StagePlan(
                stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                                  grad_bytes=0, out_bytes=1e5, sync=sync,
                                  n_devices=2, gpu_type="V100")
                        for i in range(2)],
                placement=(0, 1), n_micro=2)
            sp, fns, keys, tied = split_model(cfg, params, 2)
            runner = PipelineRunner(
                fns, plan, [devs[:2], devs[2:]], schedule="1f1b",
                n_micro=2, mb_keys=keys, tied_ref=tied)
            grads, stats = runner.step(runner.place_params(sp), batch)
            errs = [
                maxerr(grads[0]["embed"], ref_grads["embed"]),
                maxerr(grads[0]["blocks"], jax.tree.map(
                    lambda a: a[:hi], ref_grads["blocks"])),
                maxerr(grads[1]["blocks"], jax.tree.map(
                    lambda a: a[hi:], ref_grads["blocks"])),
            ]
            assert max(errs) < 1e-4, (sync, errs)
        print("SYNC_OK")
    """)
    assert "SYNC_OK" in out


def test_pipeline_parity_new_schedules():
    """Interleaved-1F1B (2 stages x 2 virtual chunks) and zero-bubble
    (split B/W backward) execute end-to-end with loss and gradients
    allclose to the single-device reference — including ZB under 2-way
    stage data parallelism with AR/PS/SFB sync."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import init_params, loss_fn
        from repro.exec import PipelineRunner, split_model
        from repro.exec.stages import StagePlan, StageSpec

        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ref_loss, _ = jax.jit(
            lambda p, b: loss_fn(cfg, p, b, remat=False))(params, batch)
        ref_grads = jax.jit(jax.grad(
            lambda p, b: loss_fn(cfg, p, b, remat=False)[0]))(params, batch)

        def maxerr(a, b):
            return max(float(jnp.max(jnp.abs(x - y))) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        def plan2(n_micro, sync="allreduce", n_devices=1):
            return StagePlan(
                stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                                  grad_bytes=0, out_bytes=1e5, sync=sync,
                                  n_devices=n_devices, gpu_type="V100")
                        for i in range(2)],
                placement=(0, 1), n_micro=n_micro)

        devs = jax.devices()
        P = cfg.num_periods

        # --- zero-bubble, single-device stages
        sp, fns, keys, tied = split_model(cfg, params, 2)
        runner = PipelineRunner(fns, plan2(4), [[devs[0]], [devs[1]]],
                                schedule="zb", n_micro=4, mb_keys=keys,
                                tied_ref=tied)
        grads, stats = runner.step(runner.place_params(sp), batch)
        hi = P // 2
        errs = [maxerr(grads[0]["embed"], ref_grads["embed"]),
                maxerr(grads[0]["blocks"], jax.tree.map(
                    lambda a: a[:hi], ref_grads["blocks"])),
                maxerr(grads[1]["blocks"], jax.tree.map(
                    lambda a: a[hi:], ref_grads["blocks"])),
                maxerr(grads[1]["final_norm"], ref_grads["final_norm"])]
        assert abs(stats.loss - float(ref_loss)) < 1e-4, stats.loss
        assert max(errs) < 1e-4, ("zb", errs)
        # zb keeps 1F1B's stash (W releases before the next F acquires)
        assert stats.peak_stash == 3, stats.peak_stash

        # --- interleaved: 2 physical stages x 2 chunks = 4 virtual
        plan = plan2(4)
        splits = plan.layer_splits(P, n_chunks=2)
        sp, fns, keys, tied = split_model(cfg, params, 4, splits=splits)
        runner = PipelineRunner(fns, plan, [[devs[0]], [devs[1]]],
                                schedule="interleaved", n_micro=4,
                                n_chunks=2, mb_keys=keys, tied_ref=tied)
        grads, stats = runner.step(runner.place_params(sp), batch)
        errs = [maxerr(grads[0]["embed"], ref_grads["embed"]),
                maxerr(grads[3]["final_norm"], ref_grads["final_norm"])]
        for u, (lo, hiu) in enumerate(splits):
            if lo < hiu:
                errs.append(maxerr(grads[u]["blocks"], jax.tree.map(
                    lambda a: a[lo:hiu], ref_grads["blocks"])))
        assert abs(stats.loss - float(ref_loss)) < 1e-4, stats.loss
        assert max(errs) < 1e-4, ("interleaved", errs)

        # --- zb with 2-way stage DP per sync mode
        for sync in ("allreduce", "ps", "sfb"):
            sp, fns, keys, tied = split_model(cfg, params, 2)
            runner = PipelineRunner(
                fns, plan2(2, sync=sync, n_devices=2),
                [devs[:2], devs[2:]], schedule="zb", n_micro=2,
                mb_keys=keys, tied_ref=tied)
            grads, stats = runner.step(runner.place_params(sp), batch)
            errs = [maxerr(grads[0]["embed"], ref_grads["embed"]),
                    maxerr(grads[0]["blocks"], jax.tree.map(
                        lambda a: a[:hi], ref_grads["blocks"])),
                    maxerr(grads[1]["blocks"], jax.tree.map(
                        lambda a: a[hi:], ref_grads["blocks"]))]
            assert max(errs) < 1e-4, (sync, errs)
        print("NEW_SCHED_PARITY_OK")
    """)
    assert "NEW_SCHED_PARITY_OK" in out


def test_scan_engine_matches_eager():
    """The compiled scan engine (CompiledPipelineRunner) produces loss
    and gradients allclose to the single-device reference for ALL four
    schedule families, with O(U) recorded scan-program events instead of
    the eager engine's O(U * n_micro) — including a 2-way stage-DP SFB
    spot check where the sync collectives run inside the scan."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import init_params, loss_fn
        from repro.exec import (CompiledPipelineRunner, PipelineRunner,
                                split_model)
        from repro.exec.stages import StagePlan, StageSpec

        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ref_loss, _ = jax.jit(
            lambda p, b: loss_fn(cfg, p, b, remat=False))(params, batch)
        ref_grads = jax.jit(jax.grad(
            lambda p, b: loss_fn(cfg, p, b, remat=False)[0]))(params, batch)

        def maxerr(a, b):
            return max(float(jnp.max(jnp.abs(x - y))) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        def plan2(n_micro, sync="allreduce", n_devices=1):
            return StagePlan(
                stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                                  grad_bytes=0, out_bytes=1e5, sync=sync,
                                  n_devices=n_devices, gpu_type="V100")
                        for i in range(2)],
                placement=(0, 1), n_micro=n_micro)

        devs = jax.devices()
        P = cfg.num_periods
        hi = P // 2
        M = 4
        for sched in ("gpipe", "1f1b", "interleaved", "zb"):
            nc = 2 if sched == "interleaved" else 1
            plan = plan2(M)
            splits = plan.layer_splits(P, n_chunks=nc) if nc > 1 else None
            sp, fns, keys, tied = split_model(cfg, params, 2 * nc,
                                              splits=splits)
            runner = CompiledPipelineRunner(
                fns, plan, [[devs[0]], [devs[1]]], schedule=sched,
                n_micro=M, n_chunks=nc, mb_keys=keys, tied_ref=tied)
            grads, stats = runner.step(runner.place_params(sp), batch,
                                       record=True)
            assert abs(stats.loss - float(ref_loss)) < 1e-4, \\
                (sched, stats.loss)
            errs = [maxerr(grads[0]["embed"], ref_grads["embed"]),
                    maxerr(grads[2 * nc - 1]["final_norm"],
                           ref_grads["final_norm"])]
            if nc == 1:
                errs += [maxerr(grads[0]["blocks"], jax.tree.map(
                             lambda a: a[:hi], ref_grads["blocks"])),
                         maxerr(grads[1]["blocks"], jax.tree.map(
                             lambda a: a[hi:], ref_grads["blocks"]))]
            else:
                for u, (lo, hiu) in enumerate(splits):
                    if lo < hiu:
                        errs.append(maxerr(grads[u]["blocks"],
                            jax.tree.map(lambda a: a[lo:hiu],
                                         ref_grads["blocks"])))
            assert max(errs) < 1e-4, (sched, errs)
            # one event per scan program, mb=-1: U fwd + U bwd
            # (+ U wgrad for zb), vs the eager engine's U*M + U*M
            U = 2 * nc
            want = U * (3 if sched == "zb" else 2)
            assert len(stats.events) == want, (sched, stats.events)
            assert all(e[2] == -1 for e in stats.events), stats.events
            # scan engine is GPipe-like in memory whatever the schedule
            assert stats.peak_stash == U * M, stats.peak_stash

        # eager engine on the same plan records per-microbatch events
        sp, fns, keys, tied = split_model(cfg, params, 2)
        eager = PipelineRunner(fns, plan2(M), [[devs[0]], [devs[1]]],
                               schedule="1f1b", n_micro=M, mb_keys=keys,
                               tied_ref=tied)
        _, est = eager.step(eager.place_params(sp), batch, record=True)
        assert len(est.events) == 2 * 2 * M, len(est.events)

        # 2-way stage DP: sync collectives run inside the scan
        sp, fns, keys, tied = split_model(cfg, params, 2)
        runner = CompiledPipelineRunner(
            fns, plan2(2, sync="sfb", n_devices=2),
            [devs[:2], devs[2:]], schedule="1f1b", n_micro=2,
            mb_keys=keys, tied_ref=tied)
        grads, stats = runner.step(runner.place_params(sp), batch)
        errs = [maxerr(grads[0]["embed"], ref_grads["embed"]),
                maxerr(grads[0]["blocks"], jax.tree.map(
                    lambda a: a[:hi], ref_grads["blocks"])),
                maxerr(grads[1]["blocks"], jax.tree.map(
                    lambda a: a[hi:], ref_grads["blocks"]))]
        assert max(errs) < 1e-4, ("sfb", errs)
        print("SCAN_ENGINE_OK")
    """)
    assert "SCAN_ENGINE_OK" in out


def test_stack_microbatches_shape_guard():
    """stack_microbatches reshapes [B, ...] -> [M, B/M, ...] and rejects
    batch sizes not divisible by n_micro."""
    import numpy as np
    from repro.exec import stack_microbatches
    batch = {"tokens": np.ones((8, 16), np.int32)}
    out = stack_microbatches(batch, 4)
    assert out["tokens"].shape == (4, 2, 16)
    with pytest.raises(ValueError, match="n_micro"):
        stack_microbatches(batch, 3)


def test_pipeline_kill_and_resume_parity():
    """Checkpoint resume for pipelined training: a run killed after 2
    steps and resumed from its per-stage checkpoint produces exactly the
    same losses and final checkpoint as an uninterrupted run."""
    out = _run_subprocess("""
        import argparse, os, tempfile
        import numpy as np
        import jax
        from repro.checkpoint import load_checkpoint
        from repro.configs import get_reduced
        from repro.exec.stages import StagePlan, StageSpec
        from repro.launch.train import run_pipeline

        cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
        plan = StagePlan(
            stages=[StageSpec(i, i, [i], flops=1e9, param_bytes=0,
                              grad_bytes=0, out_bytes=1e5, n_devices=2,
                              gpu_type="V100") for i in range(2)],
            placement=(0, 1), n_micro=4, schedule="zb")

        def mkargs(**kw):
            d = dict(arch="qwen2-1.5b", batch=8, seq=16, lr=1e-3, seed=0,
                     steps=4, log_every=10, ckpt_dir="", ckpt_every=2,
                     resume=False, pipeline="auto", n_micro=4, n_chunks=2,
                     telemetry_dir="")
            d.update(kw)
            return argparse.Namespace(**d)

        tmp = tempfile.mkdtemp()
        d1, d2 = os.path.join(tmp, "a"), os.path.join(tmp, "b")
        full = run_pipeline(mkargs(ckpt_dir=d1), cfg, plan).losses
        run_pipeline(mkargs(ckpt_dir=d2, steps=2), cfg, plan)  # "killed"
        resumed = run_pipeline(mkargs(ckpt_dir=d2, resume=True), cfg,
                               plan).losses
        assert np.allclose(full[2:], resumed, atol=1e-6), (full, resumed)
        s1, t1 = load_checkpoint(d1)
        s2, t2 = load_checkpoint(d2)
        assert s1 == s2 == 4
        for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        # a single-mesh checkpoint must be rejected by the pipeline path
        print("RESUME_PARITY_OK")
    """)
    assert "RESUME_PARITY_OK" in out


def test_single_stage_split_matches_reference():
    """Degenerate 1-stage split: the composed stage fn must apply the
    decoder blocks exactly once (regression: blocks ran twice)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.exec import split_model
    from repro.models import init_params, loss_fn

    cfg = get_reduced("qwen2-1.5b").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32),
             "labels": jnp.ones((2, 8), jnp.int32)}
    ref, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b, remat=False))(
        params, batch)
    sp, fns, keys, tied = split_model(cfg, params, 1)
    assert tied is None
    loss, _ = fns[0](sp[0], None, batch)
    assert abs(float(loss) - float(ref)) < 1e-5


# ------------------------------------------------------- launcher routing

def test_train_launcher_pipeline_fallback(capsys, monkeypatch):
    """--tag-search PIPE strategies are never silently degraded: on a
    too-small host the launcher logs an explicit fallback warning."""
    from repro.core.plan import ExecutionPlan
    from repro.launch import mesh as mesh_mod
    from repro.launch.train import resolve_pipeline
    # pin the visible device count (the suite may run under a forced
    # multi-device XLA_FLAGS)
    monkeypatch.setattr(
        mesh_mod, "stage_device_sets",
        lambda sp, devices=None: sp.assign_local_devices([object()]))
    plan = ExecutionPlan(
        rules=None, grad_sync={}, zero1=False,
        summary={"options": {"PIPE": 3}},
        stage_plan=StagePlan(
            stages=[StageSpec(i, i, [i], 1e9, 0, 0, 1e5)
                    for i in range(3)],
            placement=(0, 1, 2), n_micro=4))
    assert resolve_pipeline(plan, "auto") is None     # 1 CPU < 3 stages
    out = capsys.readouterr().out
    assert "WARNING" in out and "fallback" in out
    assert resolve_pipeline(plan, "off") is None
    out = capsys.readouterr().out
    assert "off" in out
    no_spine = ExecutionPlan(rules=None, grad_sync={}, zero1=False,
                             summary={"options": {"PIPE": 1}},
                             stage_plan=None)
    assert resolve_pipeline(no_spine, "auto") is None
    assert "single-mesh" in capsys.readouterr().out


def test_lower_strategy_attaches_stage_plan():
    from repro.core.plan import lower_strategy

    class _M:
        axis_names = ("data",)
        shape = {"data": 1}
    gg = _chain_gg()
    topo = make_testbed()
    plan = lower_strategy(_pipe_strategy(gg, (0, 1)), gg, topo, _M())
    assert plan.is_pipelined and plan.stage_plan.n_stages == 2
    assert plan.summary["n_stages"] == 2
    dp = Strategy([Action((0, 1), Option.AR)] * gg.n)
    plan2 = lower_strategy(dp, gg, topo, _M())
    assert not plan2.is_pipelined and plan2.summary["n_stages"] == 0
