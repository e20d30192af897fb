"""Host milliseconds in the estimation stage (`DesignAdvisor.estimate_sizes`:
sampling, the §5.2 planner, SampleCF through the codec kernels) per
recommend of the window."""


def read(ctx):
    if not ctx.completed or "estimate" not in ctx.stages:
        return None
    return 1e3 * ctx.stages["estimate"] / ctx.completed
