"""Host milliseconds in the program's `kernel.codec` spans, the codec
kernel calls (`codec_bytes.batched_codec_bytes`: plane split, transfer,
launch, read-back), per recommend of the window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "kernel.codec")
