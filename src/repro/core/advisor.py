"""DTAc — the compression-aware physical design advisor (paper Figure 1).

Pipeline: per-query candidate generation -> compressed-size estimation
(§4-§5 framework: amortized SampleCF + deductions chosen by the greedy graph
search) -> candidate selection (top-k or Skyline, §6.1) -> enumeration
(pure/density/backtracking greedy, §6.2) -> recommendation.

`AdvisorOptions` reproduces every tool variant the paper evaluates:
  DTA      = no compression, top-k, pure greedy
  DTAc     = compression + skyline + backtrack (the full tool)
  staged   = DTA first, then compress chosen indexes (the poor decoupled
             strategy of Example 1)
  ablations= DTAc(None)/DTAc(Skyline)/DTAc(Backtrack) for Figures 12-13

Large workloads: `AdvisorOptions.compression_budget = N` advises on at
most ~N weighted representative statements instead of the raw workload
(repro.core.workload_compression), reporting a per-recommendation cost-
error certificate on the Recommendation (`compression_error_bound` /
`compression_error_rel`).  `None` (default) — and any budget >= the
statement count — runs the uncompressed pipeline bit-identically.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import candidates as cand
from . import tracing
from .compression import DEFAULT_ADVISOR_METHODS
from .cost_engine import CostEngine
from .enumeration import EnumerationResult, greedy_enumerate
from .estimation_engine import EstimationEngine
from .estimation_graph import EstimationPlanner, NodeKey, Plan
from .relation import IndexDef
from .samplecf import SampleManager
from .whatif import (Configuration, SizeProvider, WhatIfOptimizer,
                     base_configuration, storage_used)
from .workload import Query, Workload
from .workload_compression import CompressedWorkload, compress_workload


@dataclasses.dataclass
class AdvisorOptions:
    methods: Tuple[str, ...] = DEFAULT_ADVISOR_METHODS
    consider_compression: bool = True
    candidate_mode: str = "skyline"        # "skyline" | "topk"
    enumeration: str = "backtrack"         # "backtrack" | "pure" | "density"
    topk: int = 2
    max_skyline_points: int = 8
    include_clustered: bool = True
    e: float = 0.5                         # size-estimation error tolerance
    q: float = 0.9                         # ... at this confidence
    use_deduction: bool = True
    sample_seed: int = 0
    # Each engine's backend, "numpy" | "jax" (repro.core.backend).  The
    # benchmark configurations set these three: estimation and planner on
    # "jax", costing on "numpy", since the float32 jax cost engine does
    # not always take the float64 greedy's decisions.
    engine_backend: str = "numpy"          # CostEngine (costing, greedy)
    estimation_backend: str = "numpy"      # EstimationEngine (SampleCF)
    planner_backend: str = "numpy"         # PlannerEngine (§5.2 planner)
    # One name for all three: backend="jax" (or "numpy") overrides every
    # per-module *_backend above.  None keeps them as set.
    backend: Optional[str] = None

    def __post_init__(self):
        if self.backend is not None:
            from .backend import resolve
            self.engine_backend = resolve(self.backend)
            self.estimation_backend = self.backend
            self.planner_backend = self.backend
    # advise on <= ~N weighted representatives (workload compression);
    # None disables, and budget >= n_statements is an exact bypass
    compression_budget: Optional[int] = None
    # --- durability knobs for long-lived sessions (None = unbounded).
    # All three bound RECOMPUTABLE state, so results stay bit-identical;
    # see session.AdvisorSession / planner_engine.PlannerEngine.
    samplecf_cache_entries: Optional[int] = None  # LRU (NodeKey, f) cache
    max_planner_nodes: Optional[int] = None       # node-universe epoch bound
    max_replay_entries: Optional[int] = None      # replay-store bound

    @staticmethod
    def dta() -> "AdvisorOptions":
        return AdvisorOptions(consider_compression=False,
                              candidate_mode="topk", enumeration="pure")

    @staticmethod
    def dtac() -> "AdvisorOptions":
        return AdvisorOptions()


def select_candidates(costed: Sequence[cand.Candidate],
                      options: AdvisorOptions) -> List[cand.Candidate]:
    """§6.1 per-query selection switch (skyline or top-k) — one shared
    implementation for the one-shot advisor and the online session."""
    if options.candidate_mode == "skyline":
        sel = cand.select_skyline(costed)
        return cand.skyline_representatives(sel, options.max_skyline_points)
    return cand.select_topk(costed, options.topk)


def pool_with_merged(pool: Dict[Tuple, IndexDef],
                     merged_all: Sequence[IndexDef]
                     ) -> Dict[Tuple, IndexDef]:
    """Append merged candidates to the selection pool (Figure 1: Merging
    sits between candidate selection and enumeration) — shared so the
    one-shot advisor and the online session cannot drift."""
    for idx in merged_all:
        pool.setdefault(idx.key, idx)
    return pool


@tracing.traced("advisor.enumerate")
def enumerate_pool(optimizer, sizes, options: AdvisorOptions,
                   pool: Dict[Tuple, IndexDef], base: Configuration,
                   budget_bytes: float,
                   engine: CostEngine) -> EnumerationResult:
    """§6.2 greedy enumeration — one shared implementation for the
    one-shot advisor and the online session (their bit-exact parity
    contract depends on running the same code here)."""
    return greedy_enumerate(optimizer, sizes, list(pool.values()),
                            base, budget_bytes,
                            variant=options.enumeration, engine=engine)


@dataclasses.dataclass
class Recommendation:
    config: Configuration
    base: Configuration
    base_cost: float
    cost: float
    used_bytes: float
    budget_bytes: float
    estimation_cost_pages: float
    estimation_plan: Optional[Plan]
    n_sampled: int
    n_deduced: int
    candidate_count: int
    pool_size: int
    wall_seconds: float
    steps: List[str]
    # workload-compression annotations (trailing defaults keep older
    # construction sites and dataclasses.replace uses valid)
    n_statements_full: int = 0      # raw workload statement count
    n_representatives: int = 0      # statements actually advised on
    compression_error_bound: float = 0.0   # certified |C_full - C_comp|
    compression_error_rel: float = 0.0     # ... relative to `cost`

    @property
    def improvement(self) -> float:
        """Estimated runtime improvement vs. the base design (Fig. 12-17)."""
        if self.base_cost <= 0:
            return 0.0
        return 1.0 - self.cost / self.base_cost


class DesignAdvisor:
    def __init__(self, workload: Workload,
                 options: Optional[AdvisorOptions] = None):
        self.workload = workload
        self.schema = workload.schema
        self.opt = options or AdvisorOptions()
        self.sizes = SizeProvider(self.schema)
        self.optimizer = WhatIfOptimizer(workload, self.sizes)
        self.samples = SampleManager(self.schema.tables,
                                     seed=self.opt.sample_seed)
        # populated by `recommend` when workload compression engages
        self.compressed: Optional[CompressedWorkload] = None
        self.inner: Optional["DesignAdvisor"] = None

    # ------------------------------------------------------------------
    def per_query_raw(self) -> Dict[str, List[IndexDef]]:
        return {
            q.name: cand.syntactically_relevant(
                q, self.schema.tables[q.table],
                include_clustered=self.opt.include_clustered)
            for q in self.workload.queries()
        }

    @tracing.traced("advisor.candidates")
    def _candidate_universe(self) -> Tuple[Dict[str, List[IndexDef]],
                                           List[IndexDef], List[IndexDef]]:
        """One pass over candidate generation + compression expansion.

        Returns (per-query expanded candidates, expanded merged candidates,
        the deduplicated union of both).  Everything downstream — size
        estimation, per-query costing, the enumeration pool — reuses these
        lists, so `expand_with_compression` runs once per candidate set
        instead of once in generate_candidates() and again per query.
        """
        per_query = self.per_query_raw()
        seen: Dict[Tuple, IndexDef] = {}
        for cands in per_query.values():
            for idx in cands:
                seen.setdefault(idx.key, idx)
        merged = cand.merged_candidates(per_query)
        for idx in merged:
            seen.setdefault(idx.key, idx)
        # canonical union order (raw candidates are predicate-free, so
        # (table, cols, clustered) is unique): a first-seen order would
        # reshuffle whenever an early statement leaves the workload,
        # churning the estimation targets' deduction groups for nothing —
        # sorted order is stable under workload deltas
        raw = sorted(seen.values(),
                     key=lambda i: (i.table, i.cols, i.clustered))
        if not self.opt.consider_compression:
            return per_query, merged, raw
        per_query_exp = {name: cand.expand_with_compression(c,
                                                            self.opt.methods)
                         for name, c in per_query.items()}
        merged_exp = cand.expand_with_compression(merged, self.opt.methods)
        all_cands = cand.expand_with_compression(raw, self.opt.methods)
        return per_query_exp, merged_exp, all_cands

    def generate_candidates(self) -> List[IndexDef]:
        return self._candidate_universe()[2]

    # ------------------------------------------------------------------
    @staticmethod
    def estimation_targets(all_cands: Sequence[IndexDef]
                           ) -> Dict[NodeKey, List[IndexDef]]:
        """Size-estimation targets of a candidate set: the compressed,
        predicate-free candidates, deduplicated (in order) into NodeKeys
        mapped to their IndexDef variants.  Shared with the estimation
        benchmark and parity tests so they measure exactly the target
        set the advisor estimates."""
        tkey_to_defs: Dict[NodeKey, List[IndexDef]] = {}
        for idx in all_cands:
            if idx.compression is None or idx.predicate is not None:
                continue
            k = NodeKey(idx.table, idx.cols, idx.compression)
            tkey_to_defs.setdefault(k, []).append(idx)
        return tkey_to_defs

    @tracing.traced("advisor.estimate")
    def estimate_sizes(self, all_cands: Sequence[IndexDef]
                       ) -> Tuple[float, Optional[Plan], int, int]:
        """Register estimated sizes for every compressed candidate."""
        tkey_to_defs = self.estimation_targets(all_cands)
        targets = list(tkey_to_defs)
        if not targets:
            return 0.0, None, 0, 0

        # one-shot planner: skip the cross-run replay bookkeeping the
        # persistent AdvisorSession planner records
        planner = EstimationPlanner(self.schema.tables,
                                    backend=self.opt.planner_backend,
                                    record=False)
        if self.opt.use_deduction:
            plan = planner.plan(targets, self.opt.e, self.opt.q)
        else:
            # "All": SampleCF on every target (the paper's baseline),
            # scanning the f grid for the cheapest fraction that satisfies
            # the (e, q) constraint without deductions.
            plan = planner.plan_all_sampled(targets, self.opt.e, self.opt.q)
        engine = EstimationEngine(self.schema.tables, self.samples,
                                  backend=self.opt.estimation_backend)
        ests = planner.execute(plan, self.samples, engine=engine)
        # execute() also resolves intermediate plan nodes; only register
        # sizes for defs that were actually requested as targets.
        for k, est in ests.items():
            for idx in tkey_to_defs.get(k, ()):
                self.sizes.register(idx, est.est_bytes)
        return plan.total_cost, plan, plan.n_sampled(), plan.n_deduced()

    # ------------------------------------------------------------------
    # Pipeline stages.  `recommend` composes them; the online
    # `repro.core.session.AdvisorSession` invokes them selectively with
    # its incremental caches.  This one-shot composition is the frozen
    # parity reference for the session.
    # ------------------------------------------------------------------
    def build_engine(self) -> CostEngine:
        """Stage: the batched what-if engine over the current sizes.
        Built after size estimation so every compressed candidate is
        scored with its estimated size."""
        return CostEngine(self.workload, self.sizes,
                          backend=self.opt.engine_backend)

    def select_pool(self, per_query_exp: Dict[str, List[IndexDef]],
                    merged_all: Sequence[IndexDef], base: Configuration,
                    engine: CostEngine
                    ) -> Tuple[Dict[Tuple, IndexDef], int]:
        """Stage: per-query candidate costing + §6.1 selection; merged
        candidates enter the pool directly (Figure 1: Merging sits
        between candidate selection and enumeration)."""
        pool: Dict[Tuple, IndexDef] = {}
        n_cand = 0
        for q in self.workload.queries():
            costed = cand.cost_candidates(q, per_query_exp[q.name], base,
                                          self.optimizer, self.sizes,
                                          engine=engine)
            n_cand += len(costed)
            for c in select_candidates(costed, self.opt):
                pool.setdefault(c.index.key, c.index)
        return pool_with_merged(pool, merged_all), n_cand

    def enumerate_pool(self, pool: Dict[Tuple, IndexDef],
                       base: Configuration, budget_bytes: float,
                       engine: CostEngine) -> EnumerationResult:
        """Stage: §6.2 greedy enumeration over the selected pool."""
        return enumerate_pool(self.optimizer, self.sizes, self.opt, pool,
                              base, budget_bytes, engine)

    def _recommend_full(self, budget_bytes: float) -> Recommendation:
        """The uncompressed pipeline (every statement advised directly)."""
        t0 = time.perf_counter()
        base = base_configuration(self.schema)

        per_query_exp, merged_all, all_cands = self._candidate_universe()
        est_cost, plan, n_s, n_d = self.estimate_sizes(all_cands)

        with tracing.span("advisor.cost"):
            engine = self.build_engine()
            base_cost = engine.config_cost(base)
            pool, n_cand = self.select_pool(per_query_exp, merged_all, base,
                                            engine)
        res = self.enumerate_pool(pool, base, budget_bytes, engine)
        n_full = len(self.workload.statements)
        return Recommendation(
            config=res.config, base=base, base_cost=base_cost, cost=res.cost,
            used_bytes=res.used_bytes, budget_bytes=budget_bytes,
            estimation_cost_pages=est_cost, estimation_plan=plan,
            n_sampled=n_s, n_deduced=n_d, candidate_count=n_cand,
            pool_size=len(pool), wall_seconds=time.perf_counter() - t0,
            steps=res.steps, n_statements_full=n_full,
            n_representatives=n_full)

    @tracing.traced("advisor.recommend", request=True)
    def recommend(self, budget_bytes: float) -> Recommendation:
        """Full recommendation; with `opt.compression_budget` set (and
        below the statement count) the pipeline runs on the compressed
        weighted-representative workload and the returned recommendation
        carries the certified cost-error bound.  A disabled or >= n
        budget runs `_recommend_full` — bit-identical to a pre-compression
        advisor (the exact-parity contract)."""
        comp = compress_workload(self.workload, self.opt.compression_budget)
        if comp is None:
            self.compressed = None
            self.inner = None
            return self._recommend_full(budget_bytes)
        t0 = time.perf_counter()
        inner = DesignAdvisor(
            comp.workload,
            dataclasses.replace(self.opt, compression_budget=None))
        inner.samples = self.samples   # draw-order-independent: shareable
        self.compressed = comp
        self.inner = inner
        rec = inner._recommend_full(budget_bytes)
        eps = comp.error_bound(rec.config, inner.sizes)
        return dataclasses.replace(
            rec, n_statements_full=comp.n_full,
            n_representatives=comp.n_representatives,
            compression_error_bound=eps,
            compression_error_rel=eps / max(abs(rec.cost), 1e-12),
            wall_seconds=time.perf_counter() - t0)


def staged_recommend(workload: Workload, budget_bytes: float,
                     methods: Optional[Sequence[str]] = None,
                     options: Optional[AdvisorOptions] = None
                     ) -> Recommendation:
    """The decoupled strategy of Example 1: select uncompressed indexes
    first, then compress the chosen ones to reclaim space (repeat once).

    Honors the caller's `AdvisorOptions`: stage 1 runs DTA (no
    compression) but inherits the caller's estimation settings and
    backends, stage 2 plans compressed sizes against the caller's (e, q)
    rather than a hard-coded (0.5, 0.9) and on the caller's estimation
    backend, and the stage-2/3 recompression loop is costed through the
    batched `CostEngine.config_cost` on the caller's engine backend."""
    opt = options or AdvisorOptions()
    if methods is None:
        methods = opt.methods
    stage1 = dataclasses.replace(
        AdvisorOptions.dta(), e=opt.e, q=opt.q,
        sample_seed=opt.sample_seed, include_clustered=opt.include_clustered,
        engine_backend=opt.engine_backend,
        estimation_backend=opt.estimation_backend,
        planner_backend=opt.planner_backend)
    adv = DesignAdvisor(workload, stage1)
    rec = adv.recommend(budget_bytes)
    # stage 2: compress every selected secondary index with the best method
    sizes = adv.sizes
    # register sizes for compressed variants of the chosen indexes
    chosen = [i for i in rec.config.indexes if not i.clustered]
    variants = cand.expand_with_compression(chosen, methods)
    planner = EstimationPlanner(adv.schema.tables,
                                backend=opt.planner_backend, record=False)
    targets = [NodeKey(i.table, i.cols, i.compression) for i in variants
               if i.compression is not None]
    if targets:
        plan = planner.plan(targets, opt.e, opt.q)
        engine = EstimationEngine(adv.schema.tables, adv.samples,
                                  backend=opt.estimation_backend)
        ests = planner.execute(plan, adv.samples, engine=engine)
        for k, est in ests.items():
            sizes.register(IndexDef(k.table, k.cols, k.method), est.est_bytes)
    # the recompression loop's cost oracle: the batched engine, built
    # AFTER the compressed sizes are registered so variants score with
    # their estimated sizes
    cost_fn = CostEngine(workload, sizes,
                         backend=opt.engine_backend).config_cost
    config = rec.config
    for idx in chosen:
        best = (cost_fn(config), config)
        for m in methods:
            cfg2 = config.replace(idx, idx.with_compression(m))
            c2 = cost_fn(cfg2)
            if c2 < best[0]:
                best = (c2, cfg2)
        config = best[1]
    # stage 3: with reclaimed space, account the recompressed footprint
    used = storage_used(config, rec.base, sizes)
    return dataclasses.replace(
        rec, config=config, cost=cost_fn(config), used_bytes=used)
