"""Host milliseconds in the program's `estimate.sample` spans, the sample
draws (`SampleManager.get_sample`, on a miss), per recommend of the
window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "estimate.sample")
