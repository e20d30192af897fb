"""Host milliseconds in per-query candidate costing (`cost_candidates`)
and greedy enumeration (`enumerate_pool`) per recommend of the window."""


def read(ctx):
    if not ctx.completed or "costenum" not in ctx.stages:
        return None
    return 1e3 * ctx.stages["costenum"] / ctx.completed
