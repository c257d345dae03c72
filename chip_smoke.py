"""Chip smoke test: drive the TAG trainer through its own entry points on TPU.

    python chip_smoke.py                # one chip (the default phase)
    python chip_smoke.py --four-chips   # 2 pipeline stages x 2-way DP

Default phase, on ``jax.devices()[0]`` only (even on a 4-chip host):

* the Pallas kernels, compiled for the chip, against their pure-jnp
  oracles on a small input at the widths of the configs that use them
  (flash attention at qwen2-1.5b heads, the SSD scan at mamba2-130m
  heads);
* ``repro.launch.train.run_single`` for ``STEPS`` steps of qwen2-1.5b at
  its published widths (d_model 1536, 12 q / 2 kv heads, d_ff 8960,
  vocab 151936), depth cut from 28 to 8 layers, batch 2 x seq 4096,
  ``--loss-chunk 512``, AdamW with fp32 moments, params and optimizer
  state donated. Compiled against a described v5e (see
  ``tests/test_tpu_compile.py``) this step takes 6,078 MB of aliased
  arguments and 7,776 MB of temporaries: 13,853,249,024 bytes of the
  chip's 15.75 GiB.

``--four-chips`` runs only the pipeline path and its reference: the same
8-layer config at global batch 8 x seq 2048, ``n_micro`` 4, through
``repro.launch.train.run_pipeline`` with a hand-built 2-stage x 2-way-DP
``StagePlan`` (AR gradient sync, 1F1B), once per engine (eager, scan).
Each engine's step-0 loss and global gradient norm are compared with one
``loss_fn`` value-and-grad of the same params and batch on one chip.

Earlier lines report device kind, config, compile seconds, step
milliseconds after warm-up (each step ends in ``block_until_ready``),
every loss, and device memory. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
With no TPU, the script exits non-zero before doing anything else.
"""
from __future__ import annotations

import argparse
import json
import math
from pathlib import Path
import sys
import time

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
LAYERS = 8                 # depth cut (published: 28); every width kept
STEPS = 4
ONE_CHIP = {"batch": 2, "seq": 4096, "loss_chunk": 512}
FOUR_CHIP = {"batch": 8, "seq": 2048, "n_micro": 4, "steps": 2,
             "ref_loss_chunk": 128}    # 8 x 128 tokens of logits a chunk
# The pipeline and its reference both run in bf16 but differ in how they
# sum: microbatched and data-parallel (4 microbatches x 2 shards, grads
# summed across them in bf16) against one 16k-token batch with chunked
# CE. Each reorders bf16 sums of ~2^-8 relative rounding, which average
# out over 16k tokens; a wrong stage cut, a lost microbatch or a doubled
# gradient sync moves the loss or the norm by whole percents or more.
LOSS_RTOL = 5e-3
GNORM_RTOL = 5e-2
# Kernels against f32 oracles on the chip: the MXU rounds f32 operands to
# bf16 (2^-8 relative); a mis-tiled or mis-indexed kernel errs by O(1).
KERNEL_RTOL = 5e-2

_compile_s = [0.0]


def _on_compile(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def log(msg):
    print(msg, flush=True)


def model_config(layers=LAYERS):
    from repro.configs import get_config
    return get_config(ARCH).replace(num_layers=layers)


def train_args(**kw):
    """The launcher's own CLI defaults, overridden like a user would."""
    from repro.launch.train import build_parser
    argv = ["--arch", ARCH, "--log-every", "1"]
    for k, v in kw.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    return build_parser().parse_args(argv)


def _rel_err(out, ref):
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def check_kernels(device, seq=512):
    """Flash attention and the SSD scan, compiled for the backend they
    run on, against the pure-jnp oracles at f32 matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import ref_attention, ref_ssd
    from repro.kernels.ssd_scan import ssd_scan

    qwen, mamba = get_config(ARCH), get_config("mamba2-130m")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    with jax.default_device(device):
        H, hd = qwen.num_heads, qwen.resolved_head_dim
        q, k, v = (jax.random.normal(ks[i], (1, H, seq, hd), jnp.bfloat16)
                   for i in range(3))
        out = flash_attention(q, k, v, causal=True)
        with jax.default_matmul_precision("highest"):
            ref = ref_attention(q, k, v, causal=True)
        err_flash = _rel_err(out, ref)

        nh, hd, ds = mamba.ssm_nheads, mamba.ssm_head_dim, mamba.ssm_state
        x = jax.random.normal(ks[3], (1, seq, nh, hd), jnp.bfloat16)
        dt = jax.random.uniform(ks[4], (1, seq, nh), jnp.float32, 0.01, 0.2)
        A = -jax.random.uniform(ks[5], (nh,), jnp.float32, 0.5, 2.0)
        Bm = jax.random.normal(ks[6], (1, seq, nh, ds), jnp.bfloat16)
        Cm = jax.random.normal(ks[7], (1, seq, nh, ds), jnp.bfloat16)
        y = ssd_scan(x, dt, A, Bm, Cm, chunk=mamba.ssm_chunk)
        with jax.default_matmul_precision("highest"):
            yr, _ = ref_ssd(x, dt, A, Bm, Cm)
        err_ssd = _rel_err(y, yr)
    log(f"kernels: flash (H{H} hd{qwen.resolved_head_dim} S{seq}) "
        f"rel err {err_flash:.3e}; ssd_scan (nh{nh} hd{hd} ds{ds} S{seq}) "
        f"rel err {err_ssd:.3e} (limit {KERNEL_RTOL})")
    if not (err_flash <= KERNEL_RTOL and err_ssd <= KERNEL_RTOL):
        raise AssertionError("a kernel disagrees with its oracle")


def one_chip(device, cfg, *, batch, seq, loss_chunk, steps=STEPS):
    """``run_single`` on one device; every loss and grad norm finite."""
    from repro.configs import get_config
    from repro.launch.train import run_single

    log(f"one-chip: {cfg.name} d_model={cfg.d_model} heads="
        f"{cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} layers={cfg.num_layers} (published "
        f"{get_config(ARCH).num_layers}) batch={batch} seq={seq} "
        f"loss_chunk={loss_chunk} steps={steps}")
    args = train_args(steps=steps, batch=batch, seq=seq,
                      loss_chunk=loss_chunk)
    compiled_before = _compile_s[0]
    run = run_single(args, cfg, devices=[device])
    live = device.memory_stats() or {}
    del run.params, run.opt_state
    warm = sorted(run.step_s[1:])
    log(f"one-chip: compile_s={_compile_s[0] - compiled_before:.2f} "
        f"first_step_ms={run.step_s[0] * 1e3:.1f} "
        f"step_ms_after_warmup={[round(t * 1e3, 1) for t in run.step_s[1:]]}"
        f" median={warm[len(warm) // 2] * 1e3:.1f}")
    log(f"one-chip: losses={run.losses} grad_norms={run.grad_norms}")
    log(f"one-chip: bytes_in_use={live.get('bytes_in_use')} "
        f"peak_bytes_in_use={live.get('peak_bytes_in_use')}")
    if len(run.losses) != steps or not all(
            math.isfinite(v) for v in run.losses + run.grad_norms):
        raise AssertionError("non-finite or missing loss / grad norm")


def reference(cfg, *, batch, seq, loss_chunk, device):
    """Loss and global grad norm of one ``loss_fn`` value-and-grad of the
    seed's params on the first batch, on one device, no optimizer."""
    import jax
    import jax.numpy as jnp
    from repro.data import SyntheticDataset
    from repro.models import init_params, loss_fn
    from repro.optim.adam import global_norm

    args = train_args()
    with jax.default_device(device):
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        ds = SyntheticDataset(cfg.vocab_size, seq, batch, seed=args.seed)
        b = jax.tree.map(jnp.asarray, ds.batch(0))

        @jax.jit
        def f(p):
            (loss, _), g = jax.value_and_grad(
                lambda pp: loss_fn(cfg, pp, b, loss_chunk=loss_chunk),
                has_aux=True)(p)
            return loss, global_norm(g)
        loss, gnorm = f(params)
        return float(loss), float(gnorm)


def four_chips(cfg, *, batch, seq, n_micro, steps, ref_loss_chunk):
    """``run_pipeline`` on 2 stages x 2-way DP, per engine, against the
    one-chip reference."""
    import jax
    from repro.exec.stages import StagePlan, StageSpec
    from repro.launch.train import run_pipeline

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    ref_loss, ref_gnorm = reference(cfg, batch=batch, seq=seq,
                                    loss_chunk=ref_loss_chunk,
                                    device=devices[0])
    log(f"four-chip reference (1 chip, value_and_grad): loss={ref_loss!r} "
        f"grad_norm={ref_gnorm!r}")
    plan = StagePlan(
        stages=[StageSpec(i, i, [i], flops=1.0, param_bytes=0,
                          grad_bytes=0, out_bytes=0, sync="allreduce",
                          n_devices=2, gpu_type="TPUv5e")
                for i in range(2)],
        placement=(0, 1), n_micro=n_micro, schedule="1f1b")
    ok = True
    for engine in ("eager", "scan"):
        args = train_args(steps=steps, batch=batch, seq=seq,
                          n_micro=n_micro, engine=engine, pipeline="1f1b")
        compiled_before = _compile_s[0]
        run = run_pipeline(args, cfg, plan)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        del run.params, run.opt_state
        d_loss = abs(run.losses[0] - ref_loss) / abs(ref_loss)
        d_gnorm = abs(run.grad_norms[0] - ref_gnorm) / abs(ref_gnorm)
        log(f"four-chip {engine}: compile_s="
            f"{_compile_s[0] - compiled_before:.2f} step_ms="
            f"{[round(t * 1e3, 1) for t in run.step_s]} losses={run.losses}"
            f" grad_norms={run.grad_norms}")
        log(f"four-chip {engine}: step-0 loss rel err {d_loss:.3e} "
            f"(limit {LOSS_RTOL}), grad-norm rel err {d_gnorm:.3e} "
            f"(limit {GNORM_RTOL}); bytes_in_use per device {in_use}")
        ok &= (d_loss <= LOSS_RTOL and d_gnorm <= GNORM_RTOL
               and all(math.isfinite(v) for v in run.losses)
               and all(n and n > 2**30 for n in in_use))
    if not ok:
        raise AssertionError("pipeline disagrees with the one-chip "
                             "reference, or a chip holds no state")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip pipeline phase and its "
                         "one-chip reference")
    opts = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    log(f"chip_smoke: device_kind={dev.device_kind!r} "
        f"devices={len(jax.devices())} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    t0 = time.perf_counter()
    cfg = model_config()
    if opts.four_chips:
        four_chips(cfg, **FOUR_CHIP)
        count = 4
    else:
        check_kernels(dev)
        one_chip(dev, cfg, **ONE_CHIP)
        count = 1
    log(f"chip_smoke: wall_s={time.perf_counter() - t0:.1f} "
        f"compile_s_total={_compile_s[0]:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
