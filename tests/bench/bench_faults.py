"""Faults planted underneath a benchmark run, and the control: each must
turn ``correct`` false. Used by ``test_bench_faults.py``, in-process for
the one-chip cell and in a four-device subprocess for the pipeline."""
import json
import sys

import jax
import jax.numpy as jnp


def _dup_half(batch):
    n = batch["tokens"].shape[0] // 2
    return {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}


def _double_w_down(new, old):
    ffn = new["blocks"]["layer0"]["ffn"]
    w, w0 = ffn["w_down"], old["blocks"]["layer0"]["ffn"]["w_down"]
    w2 = (2 * w.astype(jnp.float32) - w0.astype(jnp.float32)).astype(w.dtype)
    layer = {**new["blocks"]["layer0"], "ffn": {**ffn, "w_down": w2}}
    return {**new, "blocks": {"layer0": layer}}


def single_step_faults(real):
    """``launch.steps.make_train_step`` broken three ways."""
    def wrap(kind):
        def make(*a, **k):
            f = real(*a, **k)

            def step(p, s, i, b):
                if kind == "half_batch":
                    return f(p, s, i, _dup_half(b))
                p2, s2, m = f(p, s, i, b)
                if kind == "state_unchanged":
                    return p, s, m
                return _double_w_down(p2, p), s2, m      # leaf_doubled
            return step
        return make
    return {k: wrap(k) for k in ("state_unchanged", "half_batch",
                                 "leaf_doubled")}


def pipeline_step_faults(real):
    """``launch.steps.make_pipeline_train_step`` broken the same ways."""
    def wrap(kind):
        def make(opt, runner, *a, **k):
            f = real(opt, runner, *a, **k)

            def step(pl, sl, i, b, **kw):
                if kind == "half_batch":
                    return f(pl, sl, i, _dup_half(b), **kw)
                p2, s2, m = f(pl, sl, i, b, **kw)
                if kind == "state_unchanged":
                    return pl, sl, m
                return [_double_w_down(n, o) if "blocks" in n else n
                        for n, o in zip(p2, pl, strict=True)], s2, m
            return step
        return make
    return {k: wrap(k) for k in ("state_unchanged", "half_batch",
                                 "leaf_doubled")}


def drop_head_gradient(real_place):
    """``PipelineRunner.place`` that delivers zeros for the tied head's
    gradient on its way back to stage 0: the exchange left out."""
    def place(self, s, tree, *, batch=False):
        out = real_place(self, s, tree, batch=batch)
        if s == 0 and not batch and isinstance(tree, jax.Array) \
                and tree.ndim == 2:
            return jnp.zeros_like(out)
        return out
    return place


class ReferenceInPlace:
    """The control: the reference, in float8, in the program's place."""

    def __init__(self, cell, devices, precision="fp8"):
        from bench.reference import Reference
        from bench import check
        self.cell, self.devices = cell, devices
        self.ref = Reference(cell.family, cell.conf, devices,
                             precision=precision, opt=check.OPT)

    def init(self, key):
        self.ref.load(self.cell.family.make_params(self.cell.conf, key))

    def put(self, batch):
        return batch

    def step(self, i, batch):
        return self.ref.step(batch["tokens"], batch["labels"])

    def ready(self):
        pass

    def first_grad(self):
        return self.ref.first_grad

    def change(self, key):
        return self.ref.change()

    def memory_peak(self):
        return 0

    def op_labels(self, batch):
        return {}

    def free(self):
        self.ref.free()


def pipeline_main():
    """In a process with four devices: the pipeline cell sound, then under
    each fault. Prints {name: correct}."""
    from bench_tiny import run_tiny, tiny
    from repro.exec.engine import PipelineRunner
    from repro.launch import steps as steps_mod
    cell = tiny("qwen2-1.5b.pp4.b8s2k")
    out = {"sound": run_tiny(cell)[0]["correct"]}
    real = steps_mod.make_pipeline_train_step
    for name, broken in pipeline_step_faults(real).items():
        steps_mod.make_pipeline_train_step = broken
        try:
            out[name] = run_tiny(cell)[0]["correct"]
        finally:
            steps_mod.make_pipeline_train_step = real
    real_place = PipelineRunner.place
    PipelineRunner.place = drop_head_gradient(real_place)
    try:
        out["exchange_left_out"] = run_tiny(cell)[0]["correct"]
    finally:
        PipelineRunner.place = real_place
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(pipeline_main())
