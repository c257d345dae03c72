"""CPU-sized stand-ins for the benchmark's cells, and one harness run of
them past the look for a chip."""
import dataclasses

# CPU-sized stand-ins for the cells: the cell's deployment, limits and
# metrics, at widths a test run holds
TINY_CONF = {"hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_hidden_layers": 4, "vocab_size": 256}
TINY_MIX = {"batch": 4, "seq": 64, "loss_chunk": 32, "pool": 3, "n_micro": 2}
# The cells' limits (bench/limits) are set from readings at the cells' own
# sizes on the chip. At these widths bfloat16 rounding reads larger gaps,
# so runs here are held to limits set from readings here (CPU, 5 seeds):
# sound runs read loss_gap <= 3.1e-5, grad_gap <= 0.0085 and change_gap
# <= 0.0154; half the batch left out reads 0.0033 / 0.21 / 0.068, a leaf
# moved twice 0.0012 on loss_gap and 1.0 on change_gap, a state left
# unchanged 1.0.
TINY_LIMITS = {"loss_gap": 3e-4, "grad_gap": 0.05, "change_gap": 0.04}

# The float8 control needs more width than TINY_CONF to part from the
# program: here (head_dim 128, as published; CPU, 4 seeds) sound runs read
# loss_gap <= 1.9e-5 and grad_gap <= 0.0019, the control loss_gap >=
# 1.7e-4 and grad_gap >= 0.0065.
CONTROL_CONF = {"hidden_size": 256, "intermediate_size": 512,
                "num_attention_heads": 2, "num_key_value_heads": 1,
                "num_hidden_layers": 2, "vocab_size": 4096}
CONTROL_MIX = {"batch": 2, "seq": 256, "loss_chunk": 128, "pool": 2}
CONTROL_LIMITS = {"loss_gap": 6e-5, "grad_gap": 0.004, "change_gap": 0.05}


def tiny(cell_name: str, conf=TINY_CONF, mix=TINY_MIX, limits=TINY_LIMITS):
    from bench import harness
    cell = harness.load_cell(cell_name)
    return dataclasses.replace(cell, conf={**cell.conf, **conf},
                               mix={**cell.mix, **mix}, limits=limits)


def run_tiny(cell, *, seed=2**31 + 77, seconds=0.3, trace=False, **kw):
    """One harness run past the look for a chip, on the CPU's devices."""
    import io
    import json
    import time

    import jax
    from bench import harness
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, seed, seconds, trace, t0=time.perf_counter(),
                devices=jax.devices()[:cell.chips], platform_check=False,
                out=out, err=err, **kw)
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
