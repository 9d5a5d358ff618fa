"""Device time of the clients' local training per server update (ms):
operation self time in the ``fedpt/client_step`` scope, in the traced
window, over the updates. Reads ``ctx.scope_s``
(``scopes.reduce_scoped``); nothing when no operation carries the
scope."""

SCOPE = "fedpt/client_step"


def read(ctx):
    t = (getattr(ctx, "scope_s", None) or {}).get(SCOPE)
    if not t or ctx.updates == 0:
        return None
    return 1e3 * t / ctx.updates
