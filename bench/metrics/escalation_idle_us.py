"""Device-idle time per query inside the engine's ``fcvi.escalate`` spans:
the gap between stage 1's margins reaching the host and stage 2 starting on
the chip, over the queries the window's ``search`` calls served, in us
(0 where no batch escalated). Reads ``ctx.program``
(``harness/program.py``); gives nothing where the program opened no
``fcvi.`` span."""


def read(ctx):
    prog, r = getattr(ctx, "program", None), ctx.reduction
    if not prog or "fcvi.batch" not in prog or r is None or \
            r.search_queries <= 0:
        return None
    idle = prog["fcvi.escalate"].idle_s if "fcvi.escalate" in prog else 0.0
    return 1e6 * idle / r.search_queries
