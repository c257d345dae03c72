"""Blocking device-to-host reads per pipelined step: ``pipeline.sync``
spans over ``pipeline.step`` spans that start in the traced window
(``bench/spans.py``). Nothing is read where the program has no step
spans."""
from bench import spans


def read(ctx: dict) -> float | None:
    red = spans.read_trace(ctx)
    if red is None or not red["steps"]:
        return None
    return red["syncs"] / red["steps"]
