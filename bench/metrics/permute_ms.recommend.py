"""Host milliseconds in the program's `samplecf.permute` spans, SampleCF's
host prefix sorts (`estimation_engine._prefix_permutations`), per
recommend of the window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "samplecf.permute")
