"""Share of the traced window's chip-time, in percent and averaged over the
cell's devices, in which a chip was idle with no engine program queued
for it: the host had not yet issued its next work (``bench/spans.py``,
queue depth 0). ``device_idle_share`` less this is the chips waiting on
another stage or a transfer. Nothing is read where the trace shows no
device work or the program's spans name no program."""
from bench import spans


def read(ctx: dict) -> float | None:
    if not any(ctx["busy_s"]):
        return None
    red = spans.read_trace(ctx)
    if red is None or not red["starved_s"] or red["window_s"] <= 0:
        return None
    starved = [red["starved_s"][d.id] for d in ctx["devices"]]
    return 100.0 * sum(starved) / len(starved) / red["window_s"]
