"""Pipeline execution engines: run a StagePlan as a REAL multi-stage
jax train step.

Two engines share the same per-microbatch stage math (``_make_bodies``):

  * ``PipelineRunner`` executes the microbatch schedule
    (``exec.schedule``) eagerly — per-stage jitted forward / backward
    callables dispatched per event, ``device_put`` boundary transfers
    for activations and activation-grads, per-stage data parallelism
    via ``shard_map`` submeshes, and explicit AR / PS / SFB
    parameter-gradient synchronization (the §4.2.3 ILP's decisions
    routed through ``parallel.sfb_dense``'s primitives).
  * ``CompiledPipelineRunner`` rolls the same bodies into per-stage
    ``jax.lax.scan`` programs (O(stages) compiled dispatches per step,
    compile time flat in ``n_micro * n_chunks``) with bulk
    double-buffered boundary transfers; see its docstring for the
    memory/overlap trade.

Two schedule extensions execute for real here:

  * **interleaved** (virtual stages): ``n_chunks`` model chunks per
    physical stage — ``stage_fns`` has ``S * n_chunks`` entries, virtual
    stage ``u = chunk * S + s`` running on physical stage ``s``'s
    devices; chunk boundaries wrap from the last physical stage back to
    the first, exactly the extra transfers the schedule simulator
    charges.
  * **zb** (zero-bubble): the backward splits into an activation-grad
    half (``B`` events, on the cross-stage critical path) and a
    weight-grad half (``W`` events, stage-local). Each half re-runs the
    stage forward and vjp's through it, so the split costs one extra
    rematerialization — the price of freeing the B chain.

Backward recomputes the stage forward (GPipe-style rematerialization):
each backward callable re-runs the stage on the stashed *input* and
vjp's through it, so only boundary activations are stashed — the stash
count follows the schedule's ``peak_stash`` exactly (``W`` releases the
stash under zb).

Gradient semantics (proved by the parity tests): the global step loss is
the mean over microbatches of the mean over stage-DP shards of the local
loss. The engine seeds the last stage's backward with ``1/ndev_last``,
syncs parameter grads with a plain sum (psum / reduce-scatter+gather /
SFB gather-recompute), accumulates over microbatches, and divides by
``n_micro`` — bit-comparable to the single-device gradient.
"""
from __future__ import annotations

from dataclasses import dataclass, field
import time

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

from repro.exec.schedule import flatten_schedule, make_schedule
from repro.obs.spans import span
from repro.parallel.sfb_dense import tree_grad_sync
from repro.verify.diagnostics import PlanVerificationError


def named(name: str, fn):
    """``fn`` under a stable ``name``: ``jax.jit`` calls the program
    ``jit_<name>``, which is how the device trace's ``XLA Modules`` line
    tells the engine's programs apart."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


@jax.jit
def grad_accum(acc, g):
    """Add one microbatch's parameter gradient to the running sum: one
    program (``jit_grad_accum``) instead of one eager add per leaf."""
    return jax.tree.map(jnp.add, acc, g)


def _batch_spec(x, ndev: int):
    shape = getattr(x, "shape", ())
    if len(shape) >= 1 and shape[0] and shape[0] % ndev == 0:
        return P("dp", *([None] * (len(shape) - 1)))
    return P()


def _specs(tree, ndev: int):
    return jax.tree.map(lambda x: _batch_spec(x, ndev), tree)


def _gather(tree, specs):
    """All-gather the batch-sharded leaves (SFB: move the sufficient
    factors, not the parameter gradients)."""
    if tree is None:
        return None

    def g(x, spec):
        if spec is not None and "dp" in [a for a in spec if a]:
            return jax.lax.all_gather(x, "dp", tiled=True)
        return x
    return jax.tree.map(g, tree, specs)


def stack_microbatches(batch: dict, n_micro: int) -> dict:
    """Reshape every batch leaf to ``[n_micro, per_mb, ...]`` — the scan
    engine's stacked layout; row ``m`` is exactly
    ``split_microbatches(batch, n_micro)[m]``."""
    for k, v in batch.items():
        if v.shape[0] % n_micro:
            raise ValueError(
                f"batch dim {v.shape[0]} of {k!r} not divisible by "
                f"n_micro={n_micro}")
    return {k: v.reshape(n_micro, v.shape[0] // n_micro, *v.shape[1:])
            for k, v in batch.items()}


def split_microbatches(batch: dict, n_micro: int) -> list:
    """Split every batch leaf into ``n_micro`` equal chunks on dim 0."""
    sizes = {k: v.shape[0] for k, v in batch.items()}
    for k, b in sizes.items():
        if b % n_micro:
            raise ValueError(
                f"batch dim {b} of {k!r} not divisible by "
                f"n_micro={n_micro}")
    out = []
    for m in range(n_micro):
        out.append({k: v[m * (v.shape[0] // n_micro):
                         (m + 1) * (v.shape[0] // n_micro)]
                    for k, v in batch.items()})
    return out


@dataclass
class StepStats:
    loss: float
    metrics: dict
    wall_time: float
    events: list = field(default_factory=list)  # (kind, stage, mb, dur,
    #                                              chunk, start) — start
    #                                              is seconds from step
    #                                              begin
    peak_stash: int = 0


class PipelineRunner:
    """Execute stage functions under a microbatch schedule.

    ``stage_fns[u]`` has signature ``fn(params_u, carry, mb) -> carry``
    (``(loss, metrics)`` for the last virtual stage); with
    ``n_chunks > 1`` there are ``S * n_chunks`` virtual stages, virtual
    stage ``u`` running on physical stage ``u % S``. ``device_sets[s]``
    lists the jax devices hosting physical stage ``s`` (>1 devices =
    per-stage data parallelism over a "dp" submesh, grad sync per
    ``plan.stages[s].sync``). ``mb_keys[u]`` names the microbatch
    entries virtual stage ``u`` consumes (default: all).
    """

    def __init__(self, stage_fns, plan, device_sets, *,
                 schedule: str = "1f1b", n_micro: int | None = None,
                 n_chunks: int = 1, mb_keys=None, tied_ref=None,
                 store=None, graph_fp: str = "", topo_fp: str = "",
                 meta: dict | None = None, spool=None):
        self.fns = list(stage_fns)
        self.plan = plan
        self.S = len(device_sets)
        self.V = max(1, int(n_chunks))
        if self.V > 1 and schedule != "interleaved":
            # only the interleaved generator emits chunked events; any
            # other schedule would leave virtual stages S..U-1 unscheduled
            # and fail deep inside the event loop
            raise ValueError(
                f"n_chunks={self.V} requires schedule='interleaved' "
                f"(got {schedule!r})")
        self.U = self.S * self.V
        assert len(self.fns) == self.U, (len(self.fns), self.S, self.V)
        self.device_sets = [list(d) for d in device_sets]
        self.schedule = schedule
        self.n_micro = int(n_micro or plan.n_micro)
        self.mb_keys = mb_keys
        self.tied_ref = tied_ref
        self.store = store
        # live-observability spool (obs.collector.SpoolWriter): recorded
        # step events stream into this process's shard for the
        # cross-process trace collector
        self.spool = spool
        self._spool_tracks_done = False
        self.graph_fp, self.topo_fp = graph_fp, topo_fp
        self.meta = dict(meta or {})
        self.syncs = [plan.stages[s].sync if s < len(plan.stages)
                      else "allreduce" for s in range(self.S)]
        self.meshes = [
            Mesh(np.asarray(devs), ("dp",)) if len(devs) > 1 else None
            for devs in self.device_sets]
        order = make_schedule(schedule, self.S, self.n_micro,
                              n_chunks=self.V)
        # static preflight: prove the event lists deadlock/race-free and
        # the plan's collectives well-formed for the device sets we were
        # actually handed, before any compile or transfer happens (lazy
        # import: repro.verify.verifier imports repro.exec.schedule)
        from repro.verify.verifier import (
            verify_preflight, verify_schedule)
        if getattr(plan, "n_stages", None) == self.S:
            pre = verify_preflight(
                plan, order, self.n_micro, n_chunks=self.V,
                device_counts=[len(d) for d in self.device_sets])
        else:
            pre = verify_schedule(order, self.S, self.n_micro,
                                  n_chunks=self.V)
        if pre.errors():
            raise PlanVerificationError(
                pre, context=f"pipeline preflight ({schedule}, "
                             f"S={self.S}, n_micro={self.n_micro})")
        self.flat = flatten_schedule(order, self.S, self.n_micro)
        self.has_w = any(e.kind == "W" for e in self.flat)
        self._fwd = [None] * self.U
        self._bwd = [None] * self.U          # joint (dp, dc)
        self._bwd_act = [None] * self.U      # zb: dc only
        self._bwd_wgt = [None] * self.U      # zb: dp only
        self.last_stats = None               # StepStats of the last step
        self.steps_run = 0                   # the span args' step number
        self._dev_ids = [",".join(str(d.id) for d in devs)
                         for devs in self.device_sets]

    # ------------------------------------------------------- placement
    def phys(self, u: int) -> int:
        """Physical stage hosting virtual stage ``u``."""
        return u % self.S

    def _ndev(self, s: int) -> int:
        return len(self.device_sets[s])

    def place(self, s: int, tree, *, batch: bool = False):
        """Commit a pytree to physical stage ``s``'s devices (replicated
        params, batch-sharded activations on multi-device stages)."""
        if tree is None:
            return None
        mesh = self.meshes[s]
        if mesh is None:
            return jax.device_put(tree, self.device_sets[s][0])
        ndev = self._ndev(s)
        specs = _specs(tree, ndev) if batch \
            else jax.tree.map(lambda _: P(), tree)
        shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(tree, shardings)

    def device_ids(self, u: int) -> str:
        """Ids of the devices hosting virtual stage ``u``, as the spans'
        ``devices`` arg (``"0"``, ``"0,1"``)."""
        return self._dev_ids[self.phys(u)]

    def _transfer(self, s: int, tree, what: str, tags: dict):
        """A boundary ``place`` under a ``pipeline.transfer`` span."""
        with span("pipeline.transfer", "pipeline", what=what, **tags):
            return self.place(s, tree, batch=True)

    def place_params(self, params_list) -> list:
        return [self.place(self.phys(u), p)
                for u, p in enumerate(params_list)]

    def _mb_for(self, u: int, mb: dict) -> dict:
        if self.mb_keys is None:
            return mb
        return {k: mb[k] for k in self.mb_keys[u] if k in mb}

    # ------------------------------------------------------- compiled fns
    def _make_bodies(self, u: int, p_ex, c_ex, mb_ex) -> dict:
        """Un-jitted per-microbatch bodies of virtual stage ``u`` — the
        single source of the stage math both engines compile. The eager
        engine jits each body and dispatches it per event; the scan
        engine rolls the same bodies into per-stage ``lax.scan``
        programs, so gradient parity between the engines is structural.
        Multi-device stages also carry the shard_map partition specs
        (``mesh`` is None on single-device stages)."""
        fn = self.fns[u]
        is_last = u == self.U - 1
        s = self.phys(u)
        ndev = self._ndev(s)
        mesh = self.meshes[s]
        sync = self.syncs[s]

        if mesh is None:
            if is_last:
                def fwd(p, c, mb):
                    loss, mets = fn(p, c, mb)
                    return loss[None], jax.tree.map(lambda v: v[None], mets)

                def f_of(p, c, mb):
                    return fn(p, c, mb)[0]
            else:
                fwd = fn
                f_of = fn

            def bwd(p, c, mb, dout):
                _, vjp = jax.vjp(lambda pp, cc: f_of(pp, cc, mb), p, c)
                return vjp(dout)

            def bwd_act(p, c, mb, dout):
                _, vjp = jax.vjp(lambda cc: f_of(p, cc, mb), c)
                return vjp(dout)[0]

            def bwd_wgt(p, c, mb, dout):
                _, vjp = jax.vjp(lambda pp: f_of(pp, c, mb), p)
                return vjp(dout)[0]

            return {"mesh": None, "fwd": fwd, "bwd": bwd,
                    "bwd_act": bwd_act, "bwd_wgt": bwd_wgt}

        p_specs = jax.tree.map(lambda _: P(), p_ex)
        c_specs = _specs(c_ex, ndev)
        mb_specs = _specs(mb_ex, ndev)

        if is_last:
            def fwd_body(p, c, mb):
                loss, mets = fn(p, c, mb)
                return loss[None], jax.tree.map(lambda v: v[None], mets)
            mets_ex = jax.eval_shape(fn, p_ex, c_ex, mb_ex)[1]
            fwd_out_specs = (P("dp"),
                             jax.tree.map(lambda _: P("dp"), mets_ex))
            dout_specs = P()
        else:
            fwd_body = fn
            out_ex = jax.eval_shape(fn, p_ex, c_ex, mb_ex)
            fwd_out_specs = _specs(out_ex, ndev)
            dout_specs = fwd_out_specs                  # cotangent of out

        def f_loc(p, c, mb):
            return fn(p, c, mb)[0] if is_last else fn(p, c, mb)

        def dp_of(p, c, mb, dout):
            """Parameter gradient with the stage's sync mode applied."""
            if sync == "sfb":
                # sufficient factors (inputs + output grads) on the wire,
                # parameter grads recomputed locally on the full batch
                c_g = _gather(c, c_specs)
                mb_g = _gather(mb, mb_specs)
                if is_last:
                    seed = dout * ndev          # 1/ndev -> 1: gathered
                    #                             loss is the global mean
                else:
                    seed = _gather(dout, dout_specs)
                _, vjp_g = jax.vjp(lambda pp: f_loc(pp, c_g, mb_g), p)
                dp, = vjp_g(seed)
                return dp
            _, vjp = jax.vjp(lambda pp: f_loc(pp, c, mb), p)
            dp, = vjp(dout)
            return tree_grad_sync(dp, "dp", sync, ndev)

        def dc_of(p, c, mb, dout):
            _, vjp_l = jax.vjp(lambda cc: f_loc(p, cc, mb), c)
            dc, = vjp_l(dout)
            return dc

        def bwd_body(p, c, mb, dout):
            return dp_of(p, c, mb, dout), dc_of(p, c, mb, dout)

        return {"mesh": mesh, "fwd": fwd_body, "bwd": bwd_body,
                "bwd_act": dc_of, "bwd_wgt": dp_of,
                "p_specs": p_specs, "c_specs": c_specs,
                "mb_specs": mb_specs, "fwd_out_specs": fwd_out_specs,
                "dout_specs": dout_specs}

    def _build(self, u: int, p_ex, c_ex, mb_ex):
        """Compile virtual stage ``u``'s forward and backward callables
        (joint backward, plus the split activation-grad / weight-grad
        pair when the schedule zero-bubbles)."""
        B = self._make_bodies(u, p_ex, c_ex, mb_ex)
        mesh = B["mesh"]
        if mesh is None:
            self._fwd[u] = jax.jit(named("stage_fwd", B["fwd"]))
            if self.has_w:
                self._bwd_act[u] = jax.jit(named("stage_bwd_act",
                                                 B["bwd_act"]))
                self._bwd_wgt[u] = jax.jit(named("stage_bwd_wgt",
                                                 B["bwd_wgt"]))
            else:
                self._bwd[u] = jax.jit(named("stage_bwd", B["bwd"]))
            return

        self._fwd[u] = jax.jit(named("stage_fwd", shard_map(
            B["fwd"], mesh=mesh,
            in_specs=(B["p_specs"], B["c_specs"], B["mb_specs"]),
            out_specs=B["fwd_out_specs"], check_vma=False)))
        in_specs = (B["p_specs"], B["c_specs"], B["mb_specs"],
                    B["dout_specs"])
        if self.has_w:
            self._bwd_act[u] = jax.jit(named("stage_bwd_act", shard_map(
                B["bwd_act"], mesh=mesh, in_specs=in_specs,
                out_specs=B["c_specs"], check_vma=False)))
            self._bwd_wgt[u] = jax.jit(named("stage_bwd_wgt", shard_map(
                B["bwd_wgt"], mesh=mesh, in_specs=in_specs,
                out_specs=B["p_specs"], check_vma=False)))
        else:
            self._bwd[u] = jax.jit(named("stage_bwd", shard_map(
                B["bwd"], mesh=mesh, in_specs=in_specs,
                out_specs=(B["p_specs"], B["c_specs"]), check_vma=False)))

    # ------------------------------------------------------------- step
    def step(self, params_list, batch, *, record: bool = False) -> tuple:
        """One pipelined train step, under a ``pipeline.step`` span.

        Returns ``(grads_list, StepStats)``; grads match the structure of
        ``params_list`` (one entry per virtual stage; tied-head gradient
        already folded back into the stage-0 embedding).
        """
        self.steps_run += 1
        with span("pipeline.step", "pipeline", step=self.steps_run):
            return self._step(params_list, batch, record=record)

    def _read(self, x, what: str, **tags) -> float:
        """One blocking device-to-host read, under a ``pipeline.sync``
        span that says what it reads."""
        with span("pipeline.sync", "pipeline", what=what,
                  step=self.steps_run, **tags):
            return float(x)

    def _step(self, params_list, batch, *, record: bool) -> tuple:
        t_start = time.perf_counter()
        record = record or self.spool is not None   # spooling needs events
        mbs = split_microbatches(batch, self.n_micro)
        S, U, M = self.S, self.U, self.n_micro
        k = self.steps_run

        params_eff = list(params_list)
        if self.tied_ref is not None:
            src_key, dst_key = self.tied_ref
            with span("pipeline.tied_head", "pipeline", step=k,
                      stage=self.phys(U - 1), devices=self.device_ids(U - 1)):
                head = self.place(self.phys(U - 1), params_list[0][src_key])
            params_eff[U - 1] = dict(params_list[U - 1], **{dst_key: head})

        mb_cache: dict = {}             # (u, m) -> placed microbatch

        def mb_at(u, m, tags):
            if (u, m) not in mb_cache:
                mb_cache[(u, m)] = self._transfer(
                    self.phys(u), self._mb_for(u, mbs[m]), "mb", tags)
            return mb_cache[(u, m)]

        outs: dict = {}                 # (u, m) -> stage output carry
        stage_in: dict = {}             # (u, m) -> placed input (stash)
        dcs: dict = {}                  # (u, m) -> d loss / d input of u
        w_dout: dict = {}               # (u, m) -> dout stashed for W (zb)
        grads: list = [None] * U
        losses, mets_acc = [], []
        events, stash, peak = [], 0, 0
        seed_last = 1.0 / self._ndev(self.phys(U - 1))

        for ev in self.flat:
            s, m = ev.stage, ev.mb
            u = ev.chunk * S + s
            tags = {"step": k, "stage": s, "mb": m, "chunk": ev.chunk,
                    "devices": self._dev_ids[s]}
            t0 = time.perf_counter()
            if ev.kind == "F":
                with span("pipeline.F", "pipeline", program="stage_fwd",
                          **tags):
                    carry = None
                    if u > 0:
                        carry = self._transfer(s, outs.pop((u - 1, m)),
                                               "carry", tags)
                    stage_in[(u, m)] = carry
                    stash += 1
                    peak = max(peak, stash)
                    mb = mb_at(u, m, tags)
                    if self._fwd[u] is None:
                        self._build(u, params_eff[u], carry, mb)
                    out = self._fwd[u](params_eff[u], carry, mb)
                if u == U - 1:
                    loss, mets = out
                    losses.append(loss)
                    mets_acc.append(mets)
                else:
                    outs[(u, m)] = out
                done = out
            elif ev.kind == "B":
                program = "stage_bwd_act" if self.has_w else "stage_bwd"
                with span("pipeline.B", "pipeline", program=program,
                          **tags):
                    if u == U - 1:
                        dout = jnp.asarray(seed_last, jnp.float32)
                    else:
                        dout = self._transfer(s, dcs.pop((u + 1, m)),
                                              "dout", tags)
                    if self.has_w:
                        # zero-bubble: activation grad only; the stash
                        # (and dout) stay pinned until this microbatch's W
                        carry = stage_in[(u, m)]
                        dc = self._bwd_act[u](params_eff[u], carry,
                                              mb_at(u, m, tags), dout)
                        w_dout[(u, m)] = dout
                    else:
                        carry = stage_in.pop((u, m))
                        stash -= 1
                        dp, dc = self._bwd[u](params_eff[u], carry,
                                              mb_at(u, m, tags), dout)
                if u > 0:
                    dcs[(u, m)] = dc
                if self.has_w:
                    done = dc
                else:
                    grads[u] = self._accumulate(grads[u], dp, tags)
                    done = dp
            else:                       # "W": weight grad, releases stash
                with span("pipeline.W", "pipeline", program="stage_bwd_wgt",
                          **tags):
                    carry = stage_in.pop((u, m))
                    stash -= 1
                    dout = w_dout.pop((u, m))
                    dp = self._bwd_wgt[u](params_eff[u], carry,
                                          mb_at(u, m, tags), dout)
                grads[u] = self._accumulate(grads[u], dp, tags)
                done = dp
            if record:
                jax.block_until_ready(done)
                events.append((ev.kind, s, m,
                               time.perf_counter() - t0, ev.chunk,
                               t0 - t_start))

        grads = [jax.tree.map(lambda g: g / M, g_u) for g_u in grads]
        if self.tied_ref is not None:
            src_key, dst_key = self.tied_ref
            with span("pipeline.tied_grad", "pipeline", step=k, stage=0,
                      devices=self._dev_ids[0]):
                dhead = grads[U - 1].pop(dst_key)
                dhead = self.place(0, dhead)
                grads[0] = dict(grads[0], **{
                    src_key: grads[0][src_key] + dhead})

        loss = self._read(jnp.mean(jnp.concatenate(
            [jnp.atleast_1d(x) for x in losses])), "loss")
        metrics = {}
        for key in mets_acc[0]:
            metrics[key] = float(np.mean(
                [self._read(jnp.mean(mm[key]), "metric", key=key, mb=i)
                 for i, mm in enumerate(mets_acc)]))
        wall = time.perf_counter() - t_start
        stats = StepStats(loss=loss, metrics=metrics, wall_time=wall,
                          events=events, peak_stash=peak)
        self.last_stats = stats         # latest recorded step, for trace
        #                                 export (obs.trace)
        if self.store is not None:
            self._record_telemetry(stats)
        if self.spool is not None:
            self._spool_events(stats, t_start)
        return grads, stats

    def _accumulate(self, acc, g, tags: dict):
        """Running sum of one virtual stage's parameter gradient."""
        if acc is None:
            return g
        with span("pipeline.grad_accum", "pipeline", program="grad_accum",
                  **tags):
            return grad_accum(acc, g)

    # -------------------------------------------------------- telemetry
    def _record_telemetry(self, stats: StepStats):
        from repro.exec.schedule import FWD_FRAC, ZB_DGRAD_FRAC
        from repro.runtime.telemetry import StepRecord
        bwd_frac = 1.0 - FWD_FRAC
        compute, ev_meta = [], []
        for e in stats.events:
            kind, s, m, dur, chunk = e[:5]
            start = e[5] if len(e) > 5 else 0.0
            spec = self.plan.stages[s] if s < len(self.plan.stages) else None
            if spec is None:
                flops_m = 0.0
            elif m < 0:      # scan engine: one event spans all microbatches
                flops_m = spec.flops / self.V
            else:
                flops_m = spec.flops / self.n_micro / self.V
            if kind == "F":
                frac = FWD_FRAC
            elif kind == "W":
                frac = bwd_frac * (1.0 - ZB_DGRAD_FRAC)
            else:
                frac = bwd_frac * (ZB_DGRAD_FRAC if self.has_w else 1.0)
            compute.append({
                "gpu_type": getattr(spec, "gpu_type", "") or "",
                "flops": flops_m * frac, "time": dur, "op": kind,
                "stage": s, "mb": m, "kind": kind, "chunk": chunk})
            ev_meta.append({"kind": kind, "stage": s, "mb": m,
                            "chunk": chunk, "start": start,
                            "finish": start + dur})
        rec = StepRecord(
            graph_fp=self.graph_fp, topo_fp=self.topo_fp,
            wall_time=stats.wall_time, compute=compute,
            meta=dict(self.meta, executor="pipeline",
                      schedule=self.schedule, n_stages=self.S,
                      n_chunks=self.V, n_micro=self.n_micro,
                      loss=stats.loss, peak_stash=stats.peak_stash,
                      events=ev_meta))
        self.store.append(rec)

    def _spool_events(self, stats: StepStats, t_start: float):
        """Stream this step's events to the cross-process spool — one
        batched append (single lock/write) per step; event times are
        re-based from step-relative to this process's monotonic clock so
        the collector's anchor alignment applies unchanged."""
        from repro.obs.trace import KIND_LABEL, event_name
        recs = []
        if not self._spool_tracks_done:
            self._spool_tracks_done = True
            recs += [{"type": "track", "tid": s, "name": f"stage {s}"}
                     for s in range(self.S)]
        for e in stats.events:
            kind, s, m, dur, chunk = e[:5]
            start = float(e[5]) if len(e) > 5 else 0.0
            recs.append({
                "type": "span", "name": event_name(kind, s, m, chunk),
                "cat": "pipeline", "tid": int(s),
                "t0": t_start + start, "t1": t_start + start + float(dur),
                "args": {"kind": KIND_LABEL.get(kind, kind), "stage": s,
                         "mb": m, "chunk": chunk,
                         "schedule": self.schedule}})
        self.spool.emit_many(recs)


class CompiledPipelineRunner(PipelineRunner):
    """Scan-rolled pipeline engine: the same stage math as the eager
    ``PipelineRunner`` (shared un-jitted bodies, ``_make_bodies``), but
    compiled into O(U) rolled ``lax.scan`` programs instead of
    O(U * n_micro) per-event dispatches.

    Per virtual stage ``u``: one forward scan over the stacked
    microbatch axis, and one gradient-accumulating backward scan (split
    into activation-grad / weight-grad scans when the schedule
    zero-bubbles), executed in dataflow order — forwards ascending the
    virtual pipeline, backwards descending it. Gradients are
    schedule-independent (sum over microbatches / n_micro), so the
    result is parity with the eager engine under every schedule family;
    the schedule still decides validation (n_micro / chunk
    constraints), the predicted timeline, and the event program the
    verifier preflights.

    The trade the cost model and the memory prover both see:

      * boundary transfers become ONE bulk stacked ``[n_micro, ...]``
        ``device_put`` per boundary, dispatched asynchronously — the
        copy for stage u streams while jax is still executing earlier
        work (double-buffered boundaries: producer output + consumer
        copy coexist). ``exec.schedule.simulate_schedule(...,
        overlap="full")`` is this engine's timeline model.
      * every stage stashes all ``n_micro`` inputs until its backward
        (GPipe-like activation memory, whatever the schedule family);
        ``verify.memory.analyze_memory(..., engine="scan")`` proves the
        budget under that accounting.

    ``unroll`` forwards to ``lax.scan`` — the default 1 keeps the
    compiled program (and compile time) flat in ``n_micro * n_chunks``;
    larger values trade compile time for less loop overhead.
    """

    def __init__(self, *args, unroll: int = 1, **kw):
        super().__init__(*args, **kw)
        self.unroll = max(1, int(unroll))
        self._fscan = [None] * self.U
        self._bscan = [None] * self.U        # joint (dp sum, dcs)
        self._bscan_act = [None] * self.U    # zb: dcs only
        self._bscan_wgt = [None] * self.U    # zb: dp sum only

    # ------------------------------------------------------- placement
    def place_stacked(self, s: int, tree):
        """Commit stacked ``[n_micro, batch, ...]`` activations to
        physical stage ``s``: microbatch axis unsharded, per-microbatch
        batch axis sharded over the stage's "dp" submesh."""
        if tree is None:
            return None
        mesh = self.meshes[s]
        if mesh is None:
            return jax.device_put(tree, self.device_sets[s][0])
        ndev = self._ndev(s)

        def spec(x):
            shape = getattr(x, "shape", ())
            if len(shape) >= 2 and shape[1] and shape[1] % ndev == 0:
                return P(None, "dp", *([None] * (len(shape) - 2)))
            return P()
        shardings = jax.tree.map(lambda x: NamedSharding(mesh, spec(x)),
                                 tree)
        return jax.device_put(tree, shardings)

    @staticmethod
    def _stack_specs(specs):
        """Partition specs of per-microbatch values, lifted to the
        stacked layout (unsharded microbatch axis prepended)."""
        return jax.tree.map(lambda sp: P(None, *sp), specs,
                            is_leaf=lambda x: isinstance(x, P))

    # ----------------------------------------------------- compiled fns
    def _build_scan(self, u: int, p_ex, cs_ex, mbs_ex):
        """Compile virtual stage ``u``'s scan programs from the shared
        bodies: a forward scan over the microbatch axis and a backward
        scan accumulating the parameter gradient in its carry (split
        activation-grad / weight-grad scans under zero-bubble)."""
        def one(t):
            return jax.tree.map(lambda x: x[0], t)
        c_ex = one(cs_ex) if cs_ex is not None else None
        B = self._make_bodies(u, p_ex, c_ex, one(mbs_ex))
        unroll = self.unroll
        has_c = cs_ex is not None

        def xs_of(cs, mbs, douts=None):
            xs = {"mb": mbs}
            if has_c:
                xs["c"] = cs
            if douts is not None:
                xs["dout"] = douts
            return xs

        def f_scan(p, cs, mbs):
            def body(_, x):
                return 0, B["fwd"](p, x.get("c"), x["mb"])
            return jax.lax.scan(body, 0, xs_of(cs, mbs),
                                unroll=unroll)[1]

        def zeros_like_p(p):
            return jax.tree.map(jnp.zeros_like, p)

        def b_scan(p, cs, mbs, douts):
            def body(acc, x):
                dp, dc = B["bwd"](p, x.get("c"), x["mb"], x["dout"])
                return jax.tree.map(jnp.add, acc, dp), dc
            return jax.lax.scan(body, zeros_like_p(p),
                                xs_of(cs, mbs, douts), reverse=True,
                                unroll=unroll)

        def b_scan_act(p, cs, mbs, douts):
            def body(_, x):
                return 0, B["bwd_act"](p, x.get("c"), x["mb"], x["dout"])
            return jax.lax.scan(body, 0, xs_of(cs, mbs, douts),
                                reverse=True, unroll=unroll)[1]

        def b_scan_wgt(p, cs, mbs, douts):
            def body(acc, x):
                dp = B["bwd_wgt"](p, x.get("c"), x["mb"], x["dout"])
                return jax.tree.map(jnp.add, acc, dp), 0
            return jax.lax.scan(body, zeros_like_p(p),
                                xs_of(cs, mbs, douts), reverse=True,
                                unroll=unroll)[0]

        mesh = B["mesh"]
        if mesh is None:
            self._fscan[u] = jax.jit(named("stage_fwd_scan", f_scan))
            if self.has_w:
                self._bscan_act[u] = jax.jit(named("stage_bwd_act_scan",
                                                   b_scan_act))
                self._bscan_wgt[u] = jax.jit(named("stage_bwd_wgt_scan",
                                                   b_scan_wgt))
            else:
                self._bscan[u] = jax.jit(named("stage_bwd_scan", b_scan))
            return

        cs_specs = self._stack_specs(B["c_specs"])
        mbs_specs = self._stack_specs(B["mb_specs"])
        outs_specs = self._stack_specs(B["fwd_out_specs"])
        douts_specs = self._stack_specs(B["dout_specs"])
        p_specs = B["p_specs"]
        self._fscan[u] = jax.jit(named("stage_fwd_scan", shard_map(
            f_scan, mesh=mesh, in_specs=(p_specs, cs_specs, mbs_specs),
            out_specs=outs_specs, check_vma=False)))
        in_specs = (p_specs, cs_specs, mbs_specs, douts_specs)
        if self.has_w:
            self._bscan_act[u] = jax.jit(named("stage_bwd_act_scan", shard_map(
                b_scan_act, mesh=mesh, in_specs=in_specs,
                out_specs=cs_specs, check_vma=False)))
            self._bscan_wgt[u] = jax.jit(named("stage_bwd_wgt_scan", shard_map(
                b_scan_wgt, mesh=mesh, in_specs=in_specs,
                out_specs=p_specs, check_vma=False)))
        else:
            self._bscan[u] = jax.jit(named("stage_bwd_scan", shard_map(
                b_scan, mesh=mesh, in_specs=in_specs,
                out_specs=(p_specs, cs_specs), check_vma=False)))

    # ------------------------------------------------------------- step
    def _step(self, params_list, batch, *, record: bool) -> tuple:
        """One pipelined train step via the scan programs.

        Returns ``(grads_list, StepStats)`` under the same gradient
        contract as the eager engine. ``StepStats.events`` holds ONE
        entry per scan program (``mb == -1``: all microbatches), so a
        step dispatches ``U * 2`` (``U * 3`` for zero-bubble) compiled
        calls instead of the eager engine's ``U * n_micro`` and up.
        """
        t_start = time.perf_counter()
        record = record or self.spool is not None   # spooling needs events
        S, U, M = self.S, self.U, self.n_micro
        stacked = stack_microbatches(batch, M)

        params_eff = list(params_list)
        if self.tied_ref is not None:
            src_key, dst_key = self.tied_ref
            head = self.place(self.phys(U - 1), params_list[0][src_key])
            params_eff[U - 1] = dict(params_list[U - 1],
                                     **{dst_key: head})

        mbs_cache: list = [None] * U

        def mb_at(u):
            if mbs_cache[u] is None:
                mbs_cache[u] = self.place_stacked(
                    self.phys(u), self._mb_for(u, stacked))
            return mbs_cache[u]

        stage_in: list = [None] * U     # stacked stashed inputs (all M)
        fouts: list = [None] * U
        losses = mets = None
        events: list = []

        for u in range(U):
            s = self.phys(u)
            t0 = time.perf_counter()
            cs = None
            if u > 0:
                # double-buffered boundary: one bulk stacked device_put,
                # dispatched asynchronously — the copy streams while jax
                # still executes the producer's scan
                cs = self.place_stacked(s, fouts[u - 1])
                fouts[u - 1] = None
            stage_in[u] = cs
            mbs = mb_at(u)
            if self._fscan[u] is None:
                self._build_scan(u, params_eff[u], cs, mbs)
            out = self._fscan[u](params_eff[u], cs, mbs)
            if u == U - 1:
                losses, mets = out
            else:
                fouts[u] = out
            if record:
                jax.block_until_ready(out)
                events.append(("F", s, -1, time.perf_counter() - t0,
                               u // S, t0 - t_start))

        grads: list = [None] * U
        seed_last = 1.0 / self._ndev(self.phys(U - 1))
        dcs = None
        for u in reversed(range(U)):
            s = self.phys(u)
            t0 = time.perf_counter()
            if u == U - 1:
                douts = self.place_stacked(
                    s, jnp.full((M,), seed_last, jnp.float32))
            else:
                douts = self.place_stacked(s, dcs)
            cs, mbs = stage_in[u], mb_at(u)
            if self.has_w:
                dcs = self._bscan_act[u](params_eff[u], cs, mbs, douts)
                if record:
                    jax.block_until_ready(dcs)
                    events.append(("B", s, -1,
                                   time.perf_counter() - t0, u // S,
                                   t0 - t_start))
                t1 = time.perf_counter()
                grads[u] = self._bscan_wgt[u](params_eff[u], cs, mbs,
                                              douts)
                if record:
                    jax.block_until_ready(grads[u])
                    events.append(("W", s, -1,
                                   time.perf_counter() - t1, u // S,
                                   t1 - t_start))
            else:
                grads[u], dcs = self._bscan[u](params_eff[u], cs, mbs,
                                               douts)
                if record:
                    jax.block_until_ready(grads[u])
                    events.append(("B", s, -1,
                                   time.perf_counter() - t0, u // S,
                                   t0 - t_start))
            stage_in[u] = None

        grads = [jax.tree.map(lambda g: g / M, g_u) for g_u in grads]
        if self.tied_ref is not None:
            src_key, dst_key = self.tied_ref
            dhead = grads[U - 1].pop(dst_key)
            dhead = self.place(0, dhead)
            grads[0] = dict(grads[0], **{
                src_key: grads[0][src_key] + dhead})

        loss = self._read(jnp.mean(losses), "loss")
        metrics = {k: self._read(jnp.mean(mets[k]), "metric", key=k)
                   for k in mets}
        wall = time.perf_counter() - t_start
        stats = StepStats(loss=loss, metrics=metrics, wall_time=wall,
                          events=events, peak_stash=U * M)
        self.last_stats = stats
        if self.store is not None:
            self._record_telemetry(stats)
        if self.spool is not None:
            self._spool_events(stats, t_start)
        return grads, stats
