"""Share of similarity queries served in the window that the engine re-ran
at the escalated k' (``EngineStats.escalations / queries``), in %."""


def read(ctx):
    c = ctx.counters
    if ctx.mode != "similarity" or not c.get("queries") or \
            "escalations" not in c:
        return None
    return 100.0 * c["escalations"] / c["queries"]
