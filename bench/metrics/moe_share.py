"""Share of the device's busy time spent under the program's ``moe``
named scope, in percent, all passes (forward, recompute, backward):
device self time of the ops whose compiled metadata carries the scope
(``bench/spans.py``), over busy time. The scope holds the router, the
dispatch, the grouped expert matmuls, the combine and the shared
experts. Nothing is read where no op carries it."""
from bench import spans


def read(ctx: dict) -> float | None:
    red = spans.read_trace(ctx, with_scopes=True)
    if red is None or "moe" not in red["scope_s"]:
        return None
    busy = sum(red["busy_s"].values())
    return 100.0 * red["scope_s"]["moe"] / busy if busy > 0 else None
