"""Traffic kind `recommend`: one DBA client in a closed loop, a fresh
`DesignAdvisor` per request.

Each request is the configuration's workload (`gen.workload`, App. D.2
for TPC-H) with three draws: the insert weight (one of
`insert_weights`), a U(lo, hi) multiplier on each query's weight
(`query_weight_range`), and the budget as a fraction of the base design's
bytes (one of `budget_fractions`).  Requests come in blocks that hold
every (insert weight, budget) pair once, in an order drawn from the
seed, so every seed does the same mix of work.

The greedy's scoring programs take shapes that follow its path, and so
the draws.  Warm-up runs `warmup_blocks` blocks drawn from another
stream of the seed: the same kind of requests, none of the window's.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from bench import sut
from bench.gen import QueryData
from bench.ref.estimate import uncompressed_payload_bytes

WINDOW_STREAM, WARMUP_STREAM = 0, 1


def base_bytes(schema) -> float:
    """Bytes of the base design: every table's uncompressed heap."""
    return float(sum(uncompressed_payload_bytes(
        t.nrows, [w for _, w in t.columns]) for t in schema.tables.values()))


class Mix:
    def __init__(self, params: dict, schema, seed: int, gen):
        self.p = params
        self.base = base_bytes(schema)
        self.seed = seed
        # the workload per insert weight, made once: a request only
        # redraws the weights, so the window times no generator
        self.statements = {float(iw): gen.workload(schema, insert_weight=iw)
                           for iw in params["insert_weights"]}
        self.pairs = [(float(iw), float(frac))
                      for iw in params["insert_weights"]
                      for frac in params["budget_fractions"]]

    def start(self, program_schema, options) -> None:
        self.program_schema = program_schema
        self.options = options

    def _request(self, rng: np.random.Generator, iw: float, frac: float):
        lo, hi = self.p["query_weight_range"]
        stmts = self.statements[iw]
        mult = rng.uniform(lo, hi, size=len(stmts))
        stmts = [dataclasses.replace(s, weight=s.weight * float(m))
                 if isinstance(s, QueryData) else s
                 for s, m in zip(stmts, mult)]
        budget = frac * self.base
        return lambda: self._run(stmts, budget)

    def _run(self, stmts, budget):
        from repro.core import DesignAdvisor
        adv = DesignAdvisor(sut.workload(self.program_schema, stmts),
                            self.options)
        rec = adv.recommend(budget)
        return stmts, budget, rec, dict(adv.sizes._sizes)

    def _blocks(self, stream: int) -> Iterator[list]:
        rng = np.random.default_rng([self.seed, stream])
        while True:
            yield [self._request(rng, *self.pairs[int(i)])
                   for i in rng.permutation(len(self.pairs))]

    def warmup(self) -> Iterator[int]:
        """Runs the warm-up, yielding after each block."""
        blocks = self._blocks(WARMUP_STREAM)
        for b in range(self.p["warmup_blocks"]):
            for thunk in next(blocks):
                thunk()
            yield b

    def window(self) -> Iterator:
        for block in self._blocks(WINDOW_STREAM):
            yield from block
