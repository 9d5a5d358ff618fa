"""Share of its roofline the fused aggregation tail reaches (%): the
least time the chip needs for the three kernels' bytes and operations
(``counters.agg_tail_bytes`` / ``agg_tail_flops`` at the cell's (K, N)),
the larger of bytes over HBM bandwidth and operations over peak, over
the kernels' measured time per update. Nothing when no kernel ran."""


def read(ctx):
    t = sum(ctx.reduced.kernel_s.values())
    if t <= 0 or ctx.updates == 0:
        return None
    least = max(ctx.tail_bytes_per_update / ctx.peak["hbm_bytes_per_s"],
                ctx.tail_flops_per_update / ctx.peak["bf16_flops"])
    return 100.0 * least * ctx.updates / t
