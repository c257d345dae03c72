"""Share of the ``attention`` named scope's device time spent in Pallas
kernels' ops (``tpu_custom_call`` instructions), in percent, all passes:
device self time in the trace of the measured window, by the scopes of
the compiled step's metadata, as ``attention_share`` reads them
(``bench/spans.py``). The rest of the scope is the QKV and output
projections, rotary, and whatever of the attention runs outside a
kernel. Read in one-chip cells, whose step ``spans.step_hlo`` compiles;
nothing is read where no op carries the scope, 0 where none of them is
a kernel's."""
import re

from bench import spans

KERNEL = "attention/kernel"
_KERNEL_INS = re.compile(
    r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def read(ctx: dict) -> float | None:
    path = spans.locate()
    text = None if path is None else spans.step_hlo(ctx["cell"], ctx["devices"])
    if text is None:
        return None
    op_scopes = spans.scopes_of(text)
    for ins in _KERNEL_INS.findall(text):
        if op_scopes.get(ins) == "attention":
            op_scopes[ins] = KERNEL
    red = spans.reduce(spans.events(path), [d.id for d in ctx["devices"]],
                       op_scopes)
    kernel = red["scope_s"].get(KERNEL, 0.0)
    total = kernel + red["scope_s"].get("attention", 0.0)
    return 100.0 * kernel / total if total > 0 else None
