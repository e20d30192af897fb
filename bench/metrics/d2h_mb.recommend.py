"""Megabytes the codec and planner kernel calls read back from the device
(`d2h_bytes` of `codec_bytes.counters()` plus that of
`planner_score.counters()`) per recommend of the window."""


def read(ctx):
    if (not ctx.completed or "d2h_bytes" not in ctx.codec
            or "d2h_bytes" not in ctx.planner):
        return None
    return (ctx.codec["d2h_bytes"] + ctx.planner["d2h_bytes"]) / 1e6 \
        / ctx.completed
