"""Device time per query of stage 2 (the escalation sub-batch): the union
of device operations inside the engine's ``fcvi.batch`` spans but outside
their ``fcvi.step`` spans, over the queries the window's ``search`` calls
served, in us. Reads ``ctx.program`` (``harness/program.py``); gives
nothing where the program opened no ``fcvi.`` span."""


def read(ctx):
    prog, r = getattr(ctx, "program", None), ctx.reduction
    if not prog or "fcvi.batch" not in prog or r is None or \
            r.search_queries <= 0:
        return None
    step = prog["fcvi.step"].device_s if "fcvi.step" in prog else 0.0
    return 1e6 * (prog["fcvi.batch"].device_s - step) / r.search_queries
