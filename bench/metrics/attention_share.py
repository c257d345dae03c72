"""Share of the device's busy time spent under the program's
``attention`` named scope, in percent, all passes (forward, recompute,
backward): device self time of the ops whose compiled metadata carries
the scope (``bench/spans.py``), over busy time. Nothing is read where no
op carries it."""
from bench import spans


def read(ctx: dict) -> float | None:
    red = spans.read_trace(ctx, with_scopes=True)
    if red is None or "attention" not in red["scope_s"]:
        return None
    busy = sum(red["busy_s"].values())
    return 100.0 * red["scope_s"]["attention"] / busy if busy > 0 else None
