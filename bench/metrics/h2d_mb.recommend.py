"""Megabytes the codec and planner kernel calls send to the device
(`h2d_bytes` of `codec_bytes.counters()` plus that of
`planner_score.counters()`) per recommend of the window."""


def read(ctx):
    if (not ctx.completed or "h2d_bytes" not in ctx.codec
            or "h2d_bytes" not in ctx.planner):
        return None
    return (ctx.codec["h2d_bytes"] + ctx.planner["h2d_bytes"]) / 1e6 \
        / ctx.completed
