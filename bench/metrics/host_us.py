"""Host time per query inside ``search``: the time within the harness's
``search`` spans during which no operation ran on the chip (validation,
cache keys, padding, escalation bookkeeping, the planner, copies), over
the queries those calls served, in us."""


def read(ctx):
    r = ctx.reduction
    if r is None or r.search_queries <= 0:
        return None
    return 1e6 * r.search_host_s / r.search_queries
