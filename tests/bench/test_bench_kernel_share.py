"""The reader of ``attention_kernel_share``: the ``attention`` scope's
device time split into the Pallas kernels' ops and the rest."""
import pytest

from bench import spans
from bench.metrics import attention_kernel_share

MS = 1_000_000

# a Pallas kernel's instruction as the TPU compiler prints it: one line,
# with an empty kernel_metadata attribute ahead of its op_name
KERNEL_INS = (
    '  %splash_mha_fwd_residuals.1 = (bf16[12,4096,128]) custom-call(%a, %b), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2]{0}}, '
    'frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/'
    'jvp(loss)/while/body/checkpoint/attention/vmap(jit(_splash_attention))/'
    'splash_mha_fwd_residuals/pallas_call" stack_frame_id=10}, '
    'backend_config={"custom_call_config":{"body":"TUzvUg"}}')


def _op(ins: str, op_name: str) -> str:
    return f'  %{ins} = bf16[2] fusion(%p), metadata={{op_name="{op_name}"}}'


def _program(body: list) -> str:
    return "\n".join(["HloModule jit_train_step, is_scheduled=true", "",
                      "ENTRY %main.1 (p: bf16[2]) -> bf16[2] {", *body, "}"])


STEP = _program([
    _op("fusion.1", "jit(train_step)/jvp(loss)/while/body/attention/dot_general"),
    KERNEL_INS,
    _op("fusion.2", "jit(train_step)/transpose(jvp(loss))/mlp/dot_general"),
    "  ROOT %t = (bf16[2]) tuple(%fusion.2)"])


def _trace(kernel_ms: float):
    # window 0..100 ms on device 0: attention projections 10-30, the
    # kernel 30-30+kernel_ms, the mlp after it
    k_end = 30 + kernel_ms
    return {
        "host": [("bench_window", 0, 100 * MS)],
        "devices": {0: [("%fusion.1 = x", 10 * MS, 20 * MS),
                        ("%splash_mha_fwd_residuals.1 = y", 30 * MS, kernel_ms * MS),
                        ("%fusion.2 = z", k_end * MS, 10 * MS)]},
    }


class _Dev:
    def __init__(self, i):
        self.id = i


def _ctx(monkeypatch, tmp_path, ev, text):
    path = str(tmp_path / "t.xplane.pb")
    monkeypatch.setattr(spans, "locate", lambda: path)
    monkeypatch.setattr(spans, "events", lambda p: ev)
    monkeypatch.setattr(spans, "step_hlo", lambda cell, devices: text)
    return {"devices": [_Dev(0)], "cell": None, "busy_s": [0.07]}


def test_kernel_instruction_carries_its_scope():
    assert spans.scopes_of(STEP) == {"fusion.1": "attention",
                                     "splash_mha_fwd_residuals.1": "attention",
                                     "fusion.2": "mlp"}


@pytest.mark.parametrize("kernel_ms,share", [(40, 100 * 40 / 60),
                                             (20, 100 * 20 / 40)])
def test_reader_reads_the_kernels_share(monkeypatch, tmp_path, kernel_ms, share):
    ctx = _ctx(monkeypatch, tmp_path, _trace(kernel_ms), STEP)
    assert attention_kernel_share.read(ctx) == pytest.approx(share)


def test_reader_reads_zero_with_no_kernel_under_the_scope(monkeypatch, tmp_path):
    # the jnp attention: the same scope, no kernel op in it
    text = STEP.replace(KERNEL_INS, _op(
        "splash_mha_fwd_residuals.1", "jit(train_step)/jvp(loss)/attention/exp"))
    ctx = _ctx(monkeypatch, tmp_path, _trace(40), text)
    assert attention_kernel_share.read(ctx) == 0.0


def test_reader_reads_nothing_without_the_scope(monkeypatch, tmp_path):
    text = _program([_op("fusion.1", "jit(f)/dot_general")])
    ctx = _ctx(monkeypatch, tmp_path, _trace(40), text)
    assert attention_kernel_share.read(ctx) is None


def test_reader_reads_nothing_without_a_trace(monkeypatch, tmp_path):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    assert attention_kernel_share.read(
        {"devices": [_Dev(0)], "cell": None, "busy_s": [0.1]}) is None


def test_reader_reads_nothing_in_a_pipeline_cell(monkeypatch, tmp_path):
    """``step_hlo`` compiles one-chip steps only."""
    import jax
    from bench_tiny import tiny
    monkeypatch.setattr(spans, "locate", lambda: str(tmp_path / "t.xplane.pb"))
    ctx = {"devices": jax.devices()[:1], "cell": tiny("qwen2-1.5b.pp4.b8s2k"),
           "busy_s": [0.1]}
    assert attention_kernel_share.read(ctx) is None
