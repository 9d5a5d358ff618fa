"""Device time of the held experts' grouped matmul per server update
(ms): the self time of every operation named after the ``moe_gmm``
kernel (``ctx.reduced.op_s``: the forward calls, their recomputation in
the backward pass and the input-gradient calls), over the updates.
Nothing when no such kernel ran, as in a program without it."""

KERNEL = "moe_gmm"


def read(ctx):
    t = sum(s for op, s in ctx.reduced.op_s.items() if KERNEL in op)
    if t <= 0 or ctx.updates == 0:
        return None
    return 1e3 * t / ctx.updates
