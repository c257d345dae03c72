"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the cell's devices."""


def read(ctx: dict) -> float | None:
    busy, w = ctx["busy_s"], ctx["window_s"]
    if not busy or w <= 0 or not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / w)
