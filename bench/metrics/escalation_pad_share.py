"""Share of the rows the escalation sub-batches ran that were padding:
``100 * (1 - escalations / escalation_rows)`` over the window, in %, from
the engine's counters (``EngineStats.escalation_rows`` counts each
sub-batch's power-of-two bucket). Gives nothing where the program has no
such counter or nothing escalated."""


def read(ctx):
    c = ctx.counters
    if ctx.mode != "similarity" or not c.get("escalation_rows"):
        return None
    return 100.0 * (1.0 - c["escalations"] / c["escalation_rows"])
