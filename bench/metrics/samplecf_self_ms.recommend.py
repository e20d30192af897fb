"""Host milliseconds of SampleCF's own work per (table, f) group: the
self time of the program's `estimate.samplecf` spans, less the prefix
sorts (`samplecf.permute`) and the codec calls (`kernel.codec`) inside
them, per recommend of the window."""

from bench.program_spans import per_recommend_ms


def read(ctx):
    return per_recommend_ms(ctx, "estimate.samplecf", self_time=True)
