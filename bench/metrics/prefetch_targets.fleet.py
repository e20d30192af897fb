"""SampleCF targets the fleet's cross-tenant prefetch sized
(`stats["prefetch_targets"]`) per recommend the fleet resolved in the
window (`stats["recommends"]`); the change of each over the window, as
the `fleet` kind's `window_counters()` gives it."""
import sys


def read(ctx):
    kind = sys.modules.get("bench.kinds.fleet")
    c = kind.window_counters() if kind is not None else None
    if not c or not c.get("recommends") or "prefetch_targets" not in c:
        return None
    return c["prefetch_targets"] / c["recommends"]
