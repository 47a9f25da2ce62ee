"""Device time per query of stage 1 (the main step): the union of device
operations inside the engine's ``fcvi.step`` spans, over the queries the
window's ``search`` calls served, in us. Reads ``ctx.program``, the
attribution of ``harness/program.py``; gives nothing where the program
opened no ``fcvi.`` span."""


def read(ctx):
    prog, r = getattr(ctx, "program", None), ctx.reduction
    if not prog or "fcvi.step" not in prog or r is None or \
            r.search_queries <= 0:
        return None
    return 1e6 * prog["fcvi.step"].device_s / r.search_queries
