"""Host time spent after each server update's result is back (ms per
update): the self time, in the traced window, of ``grid/bookkeeping``,
``grid/eval_fn`` and ``grid/checkpoint``, over the updates. Reads
``ctx.span_s`` (``scopes.reduce_scoped``); nothing when the trace holds
none of these spans."""

NAMES = ("grid/bookkeeping", "grid/eval_fn", "grid/checkpoint")


def read(ctx):
    spans = getattr(ctx, "span_s", None) or {}
    t = [spans[k] for k in NAMES if k in spans]
    if not t or ctx.updates == 0:
        return None
    return 1e3 * sum(t) / ctx.updates
