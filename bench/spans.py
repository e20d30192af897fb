"""Stage timers around the advisor's pipeline stages, from outside it.

`install()` wraps the stage functions as the advisor binds them, so that each call is timed on the host clock and marked in the
profiler's trace as a `bench.<stage>` span.  Only traced runs install
them; the untraced run measures the program untouched.  A stage that a
later refactor renames is left unwrapped, which nulls its metrics until
a benchmark change repoints them.
"""
from __future__ import annotations

import functools
import time
from collections import defaultdict
from typing import Callable, Dict, List, Tuple


class StageTimes:
    def __init__(self):
        self.seconds: Dict[str, float] = defaultdict(float)
        self._undo: List[Callable[[], None]] = []

    def _wrap(self, owner, attr: str, stage: str) -> None:
        import jax
        fn = getattr(owner, attr)

        @functools.wraps(fn)
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(f"bench.{stage}"):
                    return fn(*args, **kwargs)
            finally:
                self.seconds[stage] += time.perf_counter() - t0

        setattr(owner, attr, timed)
        self._undo.append(lambda: setattr(owner, attr, fn))

    def install(self) -> "StageTimes":
        from repro.core import advisor, candidates
        targets: Tuple[Tuple[object, str, str], ...] = (
            (advisor.DesignAdvisor, "estimate_sizes", "estimate"),
            (candidates, "cost_candidates", "costenum"),
            (advisor, "enumerate_pool", "costenum"),
        )
        for owner, attr, stage in targets:
            if hasattr(owner, attr):
                self._wrap(owner, attr, stage)
        return self

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    def snapshot(self) -> Dict[str, float]:
        return dict(self.seconds)


def request_span(name: str):
    """The span of one window request, for attributing idle gaps."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")
