"""Device time of the server side per server update (ms): operation self
time in the ``fedpt/agg_tail`` and ``fedpt/server_apply`` scopes, in
the traced window, over the updates. Reads ``ctx.scope_s``
(``scopes.reduce_scoped``); nothing when no operation carries either
scope."""

SCOPES = ("fedpt/agg_tail", "fedpt/server_apply")


def read(ctx):
    scopes = getattr(ctx, "scope_s", None) or {}
    t = [scopes[k] for k in SCOPES if k in scopes]
    if not t or ctx.updates == 0:
        return None
    return 1e3 * sum(t) / ctx.updates
