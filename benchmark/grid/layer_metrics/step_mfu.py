"""The whole step's share of the chip's bf16 peak: the configuration's own
``flops_per_token`` x tokens per second of the traced window, over chips x
peak. Required FLOPs only; recomputation is not counted."""


def read(ctx):
    traced = ctx["traced"]
    if not traced["seconds"] or not traced["tokens"]:
        return None
    flops = ctx["model"].flops_per_token(ctx["cfg"], ctx["traffic"])
    rate = traced["tokens"] / traced["seconds"]
    return 100.0 * flops * rate / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
