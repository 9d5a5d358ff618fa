"""Host time spent before each server update's program can run (ms per
update): the self time, in the traced window, of the grid's spans that
build and dispatch an update (sync ``grid/plan``, ``grid/cohort_batch``,
``grid/round_fn``; async ``grid/restack`` and the dispatch spans
``grid/lane_step[...]``, ``grid/client_step[...]``,
``grid/server_apply``), over the updates. Reads ``ctx.span_s``
(``scopes.reduce_scoped``); nothing when the trace holds none of these
spans."""

PREFIXES = ("grid/plan", "grid/cohort_batch", "grid/round_fn",
            "grid/restack", "grid/lane_step[", "grid/client_step[",
            "grid/server_apply")


def read(ctx):
    spans = getattr(ctx, "span_s", None) or {}
    t = [v for k, v in spans.items() if k.startswith(PREFIXES)]
    if not t or ctx.updates == 0:
        return None
    return 1e3 * sum(t) / ctx.updates
