"""The whole step's share (%) of the chips' bf16 peak: model FLOPs of forward
and backward per step (benchmark/flops/<config>.py) times the steps drained
in the window, over the window's seconds and chips x peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
