"""Share of the HBM roofline that the codec path reaches.

The work is the algorithm's, not the kernels' padded planes: every
SAMPLED node of each window recommend's estimation plan reads its
sample once, rows x key columns x 4 bytes.  The least time is that over
the chip's HBM bandwidth; the device time is the summed duration of the
codec modules (`jit(_codec_call)`: the XLA sort ahead of GDICT/LDICT, the
padding and the Pallas segment-reduce kernel) in the trace."""

from bench.peaks import peaks

CODEC_MODULE = r"_codec_call"
VALUE_BYTES = 4


def sampled_bytes(plan, nrows_of) -> int:
    total = 0
    for k, node in plan.nodes.items():
        if node.state.name != "SAMPLED":
            continue
        n = nrows_of(k.table)
        rows = min(max(2, int(round(n * plan.f))), n)
        total += rows * len(k.cols) * VALUE_BYTES
    return total


def read(ctx):
    from bench import trace_reduce
    device_s = trace_reduce.module_s(ctx.trace, CODEC_MODULE)
    plans = [r.rec.estimation_plan for r in ctx.records
             if r.rec.estimation_plan is not None]
    if device_s <= 0 or not plans:
        return None
    work = sum(sampled_bytes(p, lambda t: ctx.schema.tables[t].nrows)
               for p in plans)
    least_s = work / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
