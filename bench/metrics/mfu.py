"""Model FLOP/s utilisation of the traced window, in percent: model
FLOPs per token (``bench/families``) x tokens per second of the traced
window, over the chips' bf16 peak. Recomputation is not counted. Nothing
is read where the trace shows no device work."""
from bench.peaks import peak


def read(ctx: dict) -> float | None:
    if not any(ctx["busy_s"]):
        return None
    flops = ctx["flops_per_token"] * ctx["tokens_per_s"]
    devs = ctx["devices"]
    return 100.0 * flops / (len(devs) * peak(devs[0].device_kind)["bf16_flops"])
