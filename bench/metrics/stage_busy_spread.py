"""Imbalance of the pipeline stages, in percent: (most - least) busy time
over the stage devices, over the most. Needs more than one device."""


def read(ctx: dict) -> float | None:
    busy = ctx["busy_s"]
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
