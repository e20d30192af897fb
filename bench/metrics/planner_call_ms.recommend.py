"""Host milliseconds in the program's `kernel.planner` spans, the §5.2
planner's kernel calls (`planner_score.fused_score` and `prob_within`:
padding, transfer, launch, read-back), per recommend of the window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "kernel.planner")
