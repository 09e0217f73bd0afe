"""Model FLOPs of the traced rounds over the window, as a share of the
chips' bf16 peak (%)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"]:
        return None
    rate = ctx["flops_per_round"] * ctx["rounds"] / t["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
