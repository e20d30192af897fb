"""§5.2 planner kernel launches (`planner_score.counters()`, fused
scoring plus probability calls) per recommend of the window."""


def read(ctx):
    if not ctx.completed:
        return None
    return (ctx.planner["fused_calls"] + ctx.planner["prob_calls"]) \
        / ctx.completed
