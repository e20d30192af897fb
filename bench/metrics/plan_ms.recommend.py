"""Host milliseconds in the program's `estimate.plan` spans, the §5.2
planner (`EstimationPlanner.plan`), per recommend of the window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "estimate.plan")
