"""Codec kernel launches (`codec_bytes.counters()["kernel_calls"]`) per
recommend of the window."""


def read(ctx):
    if not ctx.completed:
        return None
    return ctx.codec["kernel_calls"] / ctx.completed
