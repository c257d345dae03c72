"""One run of one benchmark cell: set-up, the measured window, the
optional trace, and the check against the plain reference.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The configuration names its family
(``bench/families/<family>.py``: program configuration, weights, FLOP
count, reference), the mix names how the program is deployed (one jitted
step on one chip, or the eager pipeline engine over one chip per stage)
and the rows it trains on. Per-layer metrics are readers found by name
(``bench/metrics/<metric>.py``), and each cell's correctness limits are
``bench/limits/<cell>.json``. Adding any of these adds files only.

Run: set-up builds the program's step with its state, drives it through
the first ``CHECKED_STEPS`` steps with the window's own call and feed,
and keeps their losses, the first gradient (from the optimizer's first
moment) and the weights' change. Then a closed loop of synchronous steps
for ``--seconds``: each step ``device_put``s its batch, dispatches, and
waits with ``block_until_ready``. After the window the program's state is
freed and the reference repeats the first steps; the numbers compared are
printed beside their limits. The last line of standard output is one JSON
object.
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass, field
import importlib.util
import json
import math
import os
from pathlib import Path
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check, traffic
from bench.peaks import peak

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def load_module(path: Path):
    """A benchmark file found by name (a family or a metric reader)."""
    name = f"bench_{path.stem.replace('-', '_')}_{abs(hash(str(path))):x}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    limits: dict
    per_layer: list = field(default_factory=list)   # manifest entries
    root: Path = ROOT

    @property
    def family(self):
        return load_module(self.root / "bench" / "families"
                           / f"{self.conf['family']}.py")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    conf = json.loads((root / conf_entry["file"]).read_text())
    conf["name"] = w["config"]
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    limits = json.loads((root / "bench" / "limits" / f"{name}.json").read_text())
    per_layer = [m for m in manifest["per_layer"]
                 if name in m.get("workloads", [name])]
    return Cell(name, w["chips"], conf, mix, limits, per_layer, root)


def tpu_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peak(devs[0].device_kind)               # unknown kinds are an error
    return devs[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at the launcher's fixed ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def weight_key(seed: int):
    """A JAX key from any whole-number seed (more than 32 bits allowed)."""
    import jax
    word = int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.random.PRNGKey(word)


# ------------------------------------------------------------ the program

def _is_block(path) -> bool:
    return getattr(path[0], "key", None) == "blocks"


def _leaf_name(path, offset: int, i: int | None) -> str:
    from jax.tree_util import keystr
    if i is None:
        return keystr(path, simple=True, separator="/")
    return f"L{offset + i}/" + keystr(path[2:], simple=True, separator="/")


def _norm_tree(tree):
    """Per-leaf float32 norms; a stacked ``blocks`` leaf gives one per
    layer."""
    import jax
    import jax.numpy as jnp

    def one(path, a):
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if _is_block(path) else None
        return jnp.sqrt(jnp.sum(a * a, axis=axes))
    return jax.tree_util.tree_map_with_path(one, tree)


def _named(norms, offset: int = 0) -> dict:
    import jax
    out = {}
    for path, v in jax.tree_util.tree_leaves_with_path(jax.device_get(norms)):
        v = np.asarray(v, np.float64)
        if _is_block(path):
            for i, x in enumerate(v):
                out[_leaf_name(path, offset, i)] = float(x)
        else:
            out[_leaf_name(path, offset, None)] = float(v)
    return out


class _Program:
    """What both deployments share: the weights from the seed, the norms
    the check reads, the state's lifetime."""

    def __init__(self, cell: Cell, devices: list):
        import jax
        import jax.numpy as jnp
        from repro.optim.adam import AdamW
        self.cell, self.devices = cell, devices
        self.cfg = cell.family.program_config(cell.conf)
        self.opt = AdamW(lr=check.OPT.lr)
        fam, conf = cell.family, cell.conf
        self.make_params = jax.jit(lambda k: fam.make_params(conf, k))
        self._norms = jax.jit(_norm_tree)
        self._diff_norms = jax.jit(lambda a, b: _norm_tree(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
        self._check_layout()

    def _check_layout(self):
        import jax
        from repro.models import abstract_params
        want = abstract_params(self.cfg)
        fam, conf = self.cell.family, self.cell.conf
        got = jax.eval_shape(lambda k: fam.make_params(conf, k),
                             jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                                strict=True)):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)


class SingleChip(_Program):
    """``launch.train.run_single`` on one chip: the jitted train step with
    params and optimizer state donated."""

    def __init__(self, cell: Cell, devices: list):
        super().__init__(cell, devices)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import mesh as mesh_mod
        from repro.launch import steps as steps_mod
        mesh = mesh_mod.make_host_mesh(devices[:1])
        self.rep = NamedSharding(mesh, P())
        rules = steps_mod.baseline_rules(mesh)
        options = steps_mod.StepOptions(loss_chunk=cell.mix["loss_chunk"])
        self.fn = jax.jit(steps_mod.make_train_step(
            self.cfg, self.opt, rules, options), donate_argnums=(0, 1))

    def init(self, key):
        import jax
        params = self.make_params(key)
        self.state = jax.device_put((params, self.opt.init(params)), self.rep)

    def put(self, batch):
        import jax
        return jax.device_put(batch, self.rep)

    def step(self, i: int, batch):
        import jax.numpy as jnp
        params, opt_state = self.state
        params, opt_state, metrics = self.fn(
            params, opt_state, jnp.asarray(i, jnp.int32), batch)
        self.state = (params, opt_state)
        return metrics["loss"]

    def ready(self):
        import jax
        jax.block_until_ready(self.state)

    def first_grad(self) -> dict:
        mu = self.state[1]["mu"]
        return {k: v / (1 - self.opt.b1) for k, v in _named(self._norms(mu)).items()}

    def change(self, key) -> dict:
        return _named(self._diff_norms(self.state[0], self.make_params(key)))

    def op_labels(self, batch) -> dict:
        import jax.numpy as jnp
        from bench.trace import hlo_op_labels
        params, opt_state = self.state
        text = self.fn.lower(params, opt_state, jnp.asarray(0, jnp.int32),
                             batch).compile().as_text()
        return hlo_op_labels(text)

    def free(self):
        self.state = None


class Pipeline(_Program):
    """``launch.train.run_pipeline``'s eager engine: one stage per chip,
    an even ``StagePlan.layer_splits`` cut, ``runner.place_params``, and
    ``steps.make_pipeline_train_step``."""

    def __init__(self, cell: Cell, devices: list):
        super().__init__(cell, devices)
        from repro.exec.stages import StagePlan, StageSpec
        from repro.launch import mesh as mesh_mod
        mix = cell.mix
        S = len(devices)
        self.plan = StagePlan(
            stages=[StageSpec(i, i, [i], flops=1.0, param_bytes=0,
                              grad_bytes=0, out_bytes=0, sync="allreduce",
                              n_devices=1, gpu_type=devices[0].device_kind)
                    for i in range(S)],
            placement=tuple(range(S)), n_micro=mix["n_micro"],
            schedule=mix["schedule"])
        self.device_sets = mesh_mod.stage_device_sets(self.plan, devices)
        self.splits = self.plan.layer_splits(self.cfg.num_periods)

    def _split(self, params):
        from repro.exec import split_model
        return split_model(self.cfg, params, self.plan.n_stages,
                           splits=self.splits)

    def init(self, key):
        from repro.exec import PipelineRunner
        from repro.launch import steps as steps_mod
        mix = self.cell.mix
        stage_params, fns, mb_keys, tied = self._split(self.make_params(key))
        self.runner = PipelineRunner(
            fns, self.plan, self.device_sets, schedule=mix["schedule"],
            n_micro=mix["n_micro"], n_chunks=1, mb_keys=mb_keys,
            tied_ref=tied, meta={"launcher": "bench"})
        params = self.runner.place_params(stage_params)
        del stage_params
        self.state = (params, [self.opt.init(p) for p in params])
        self.fn = steps_mod.make_pipeline_train_step(self.opt, self.runner)

    def put(self, batch):
        import jax
        return jax.device_put(batch, self.devices[0])

    def step(self, i: int, batch):
        import jax.numpy as jnp
        params, opt_state = self.state
        params, opt_state, metrics = self.fn(
            params, opt_state, jnp.asarray(i, jnp.int32), batch, record=False)
        self.state = (params, opt_state)
        return metrics["loss"]

    def ready(self):
        import jax
        jax.block_until_ready(self.state)

    def first_grad(self) -> dict:
        out = {}
        for (lo, _), s in zip(self.splits, self.state[1], strict=True):
            out.update(_named(self._norms(s["mu"]), lo))
        return {k: v / (1 - self.opt.b1) for k, v in out.items()}

    def change(self, key) -> dict:
        import jax
        p0, _, _, _ = self._split(self.make_params(key))
        out = {}
        for (lo, _), p, q, devs in zip(self.splits, self.state[0], p0,
                                       self.device_sets, strict=True):
            out.update(_named(self._diff_norms(p, jax.device_put(q, devs[0])),
                              lo))
        return out

    def op_labels(self, batch) -> dict:
        return {}

    def free(self):
        self.state = self.runner = self.fn = None


DEPLOYMENTS = {"single": SingleChip, "pipeline": Pipeline}


def make_system(cell: Cell, devices: list):
    return DEPLOYMENTS[cell.mix["deployment"]](cell, devices)


# ------------------------------------------------------------ one run

def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def window(system, batches, seconds: float, first_step: int) -> dict:
    """The closed loop: steps until ``seconds`` have passed, each one
    putting its batch, dispatching, and waiting for the new state."""
    losses, ends = [], []
    i = 0
    with _annotate("bench_window"):
        t0 = time.perf_counter()
        while True:
            with _annotate("device_put"):
                b = system.put(batches[i % len(batches)])
            with _annotate("dispatch"):
                losses.append(system.step(first_step + i, b))
            with _annotate("wait"):
                system.ready()
            i += 1
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
    step_s = np.diff([t0, *ends])
    return {"steps": i, "seconds": ends[-1] - t0,
            "losses": [float(x) for x in losses],
            "step_s": {"min": float(step_s.min()),
                       "median": float(np.median(step_s)),
                       "max": float(step_s.max())}}


def checked_steps(system, checked, key) -> dict:
    """Drive the built program through the checked steps with the
    window's own call and feed; keep what the check reads."""
    out = {"losses": []}
    for i, b in enumerate(checked):
        out["losses"].append(float(system.step(i, system.put(b))))
        system.ready()
        if i == 0:
            out["grad"] = system.first_grad()
    out["change"] = system.change(key)
    return out


def reference_readings(cell: Cell, devices, key, checked, precision="f32",
                       rows=None) -> dict:
    """What the reference (or its control) reads over the checked steps.
    ``rows`` keeps only these rows of each batch (a planted fault)."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from bench.reference import Reference
    ref = Reference(cell.family, cell.conf, devices, precision=precision,
                    opt=check.OPT)
    ref.load(jax.jit(lambda k: cell.family.make_params(cell.conf, k),
                     out_shardings=SingleDeviceSharding(devices[0]))(key))
    for b in checked:
        tok, lab = b["tokens"], b["labels"]
        if rows is not None:
            tok, lab = tok[rows], lab[rows]
        ref.step(tok, lab)
    out = {"losses": ref.losses, "grad": ref.first_grad, "change": ref.change()}
    ref.free()
    return out


class _Compiles:
    """Counts backend compilations while registered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t0: float, devices=None, system_factory=make_system,
        platform_check=True, out=sys.stdout, err=sys.stderr) -> dict:
    """One run; prints the result line and returns it."""
    import jax
    if platform_check:
        devices = tpu_devices(cell.chips)
    devices = list(devices)
    enable_compile_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, trace, t0, devices, system_factory,
                    compiles, out, err)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, trace, t0, devices, system_factory, compiles,
         out, err) -> dict:
    import jax
    fam, mix = cell.family, cell.mix
    vocab = fam.program_config(cell.conf).vocab_size
    all_batches = traffic.batches(mix, vocab, seed)
    checked = all_batches[:traffic.CHECKED_STEPS]
    pool = all_batches[traffic.CHECKED_STEPS:]
    key = weight_key(seed)

    system = system_factory(cell, devices)
    system.init(key)
    program = checked_steps(system, checked, key)
    # one step of the window's own kind, from its pool, before timing
    system.step(len(checked), system.put(pool[0]))
    system.ready()
    first = len(checked) + 1
    setup_s = time.perf_counter() - t0

    n_compiles = compiles.n
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            with jax.profiler.trace(log_dir):
                win = window(system, pool, seconds, first)
        else:
            win = window(system, pool, seconds, first)
        window_compiles = compiles.n - n_compiles
        tokens_per_s = win["steps"] * mix["batch"] * mix["seq"] / win["seconds"]
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(system.memory_peak())}
        if trace:
            from bench import trace as trace_mod
            labels = system.op_labels(system.put(pool[0]))
            red = trace_mod.reduce(
                trace_mod.events(trace_mod.find_xplane(log_dir)),
                [d.id for d in devices], labels)
            busy = [red["busy_s"].get(d.id, 0.0) for d in devices]
            device.update(busy_s=float(np.mean(busy)),
                          window_s=red["window_s"])
            ctx = {"cell": cell, "devices": devices, "busy_s": busy,
                   "window_s": red["window_s"], "tokens_per_s": tokens_per_s,
                   "flops_per_token": fam.flops_per_token(cell.conf, mix["seq"])}
            metrics = {}
            for m in cell.per_layer:
                v = cell.reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            breakdown = {"device_ops": [list(x) for x in red["device_ops"]],
                         "idle_gaps": [list(x) for x in red["idle_gaps"]]}
        else:
            metrics = {"tokens_per_s": {"value": tokens_per_s,
                                        "unit": "tokens/s"},
                       "setup_s": {"value": setup_s, "unit": "s"}}
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    system.free()
    del system

    reference = reference_readings(cell, devices, key, checked)
    checks = check.compare(program, reference, cell.limits)
    failed = sum(not math.isfinite(x) for x in win["losses"])
    checks["window_nonfinite_losses"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    line = {"correct": correct, "attempted": win["steps"], "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = breakdown
    line["window_compiles"] = window_compiles
    line["window_step_s"] = win["step_s"]
    # JSON has no infinity: a gap that could not be read prints as text
    line["checks"] = {k: {**c, "value": c["value"] if math.isfinite(c["value"])
                          else str(c["value"])} for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=err)
    print(json.dumps(line), file=out, flush=True)
    return line


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        run(cell, args.seed, args.seconds, bool(args.trace), t0=t0)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    return 0
