"""Share (%) of the SAMPLED targets the fleet's prefetch peeked in the
window that were already in the share group's cache:
`prefetch_hits` / (`prefetch_hits` + `prefetch_targets`), each the
change over the window (`fleet` kind, `window_counters()`)."""
import sys


def read(ctx):
    kind = sys.modules.get("bench.kinds.fleet")
    c = kind.window_counters() if kind is not None else None
    if not c or "prefetch_hits" not in c or "prefetch_targets" not in c:
        return None
    peeked = c["prefetch_hits"] + c["prefetch_targets"]
    return 100.0 * c["prefetch_hits"] / peeked if peeked else None
