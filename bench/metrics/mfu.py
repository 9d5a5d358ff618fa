"""Model FLOP utilization of the whole step (%): the model FLOPs of the
updates completed in the traced window (``counters.step_flops`` per
example, times cohort x local steps x batch) over the window, over the
chip's bf16 peak."""


def read(ctx):
    r = ctx.reduced
    if ctx.updates == 0 or r.window_s <= 0:
        return None
    return (100.0 * ctx.flops_per_update * ctx.updates / r.window_s
            / ctx.peak["bf16_flops"])
