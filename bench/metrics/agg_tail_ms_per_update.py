"""Device time of the fused aggregation tail per server update (ms): the
summed durations of the agg_tail_stats, agg_tail_pack and agg_tail_apply
kernels over the updates. Nothing when the cell takes the staged tail."""


def read(ctx):
    t = sum(ctx.reduced.kernel_s.values())
    if t <= 0 or ctx.updates == 0:
        return None
    return 1e3 * t / ctx.updates
