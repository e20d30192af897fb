"""(tenant, query) costing jobs the fleet's stacked COST prefetch scored
(`stats["cost_prefetch_jobs"]`) per recommend the fleet resolved in the
window (`stats["recommends"]`); the change of each over the window
(`fleet` kind, `window_counters()`)."""
import sys


def read(ctx):
    kind = sys.modules.get("bench.kinds.fleet")
    c = kind.window_counters() if kind is not None else None
    if not c or not c.get("recommends") or "cost_prefetch_jobs" not in c:
        return None
    return c["cost_prefetch_jobs"] / c["recommends"]
