"""Device time per query: the union of device-operation time inside the
harness's ``search`` spans, over the queries those calls served, in us.
Needs no operation names."""


def read(ctx):
    r = ctx.reduction
    if r is None or r.search_queries <= 0 or r.search_device_s <= 0:
        return None
    return 1e6 * r.search_device_s / r.search_queries
