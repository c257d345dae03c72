"""Share of the ``moe`` named scope's device time spent in its
``experts`` sub-scope, in percent, all passes: the grouped matmuls over
the held experts' sorted rows. The rest of the scope is the router, the
sort and dispatch, the combine and the shared experts. Device self time
in the trace of the measured window, by the scopes of the compiled
step's metadata, as ``moe_share`` reads them (``bench/spans.py``), with
the sub-scope read from each instruction's ``op_name``. Read in
one-chip cells, whose step ``spans.step_hlo`` compiles; nothing is read
where no op carries the ``moe`` scope."""
import re

from bench import spans

EXPERTS = "moe/experts"
_OP_NAME = re.compile(r'%([\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]+)"')


def expert_ops(hlo_text: str, op_scopes: dict) -> set:
    """Instructions under ``moe`` whose ``op_name`` names ``experts``."""
    return {ins for ins, op_name in _OP_NAME.findall(hlo_text)
            if op_scopes.get(ins) == "moe"
            and "experts" in map(spans._unwrap, op_name.split("/"))}


def read(ctx: dict) -> float | None:
    path = spans.locate()
    text = None if path is None else spans.step_hlo(ctx["cell"], ctx["devices"])
    if text is None:
        return None
    op_scopes = spans.scopes_of(text)
    for ins in expert_ops(text, op_scopes):
        op_scopes[ins] = EXPERTS
    red = spans.reduce(spans.events(path), [d.id for d in ctx["devices"]],
                       op_scopes)
    experts = red["scope_s"].get(EXPERTS, 0.0)
    total = experts + red["scope_s"].get("moe", 0.0)
    return 100.0 * experts / total if total > 0 else None
