"""Device busy time per server update (ms): the union of the device-op
intervals in the traced window over the updates completed in it."""


def read(ctx):
    if ctx.updates == 0 or ctx.reduced.n_device_events == 0:
        return None
    return 1e3 * ctx.reduced.busy_s / ctx.updates
