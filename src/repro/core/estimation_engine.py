"""Batched SampleCF: size estimation for many targets as array code.

The scalar path (`repro.core.samplecf.sample_cf`) builds and compresses one
index per call; an estimation plan with hundreds of SAMPLED targets pays a
Python-level lexsort + five-odd NumPy kernel launches per target.  This
engine computes every SAMPLED target of a plan in a handful of grouped
kernel calls while staying byte-identical to the scalar reference.

Batch dimensions, in the paper's terms:

* **Group axis — (table, f):** the §4.1 amortization.  One uniform sample
  of fraction `f` per table is drawn (via `SampleManager`, so the sampling
  cost of §5.1 is paid once) and shared by every target on that table.
* **Target axis — (cols, method):** each target is one compressed index
  `I^c` whose SampleCF `CF = S^c / S` (§2.2) we estimate on the group's
  sample.  The §5.1 estimation cost charged per target is unchanged: the
  pages of the index built on the sample.
* **Job axis — (prefix, column):** the unit of batched work.  A target
  with key columns (c_0..c_k) needs, for each position j, the payload
  bytes of column c_j laid out in the target's sort order.  That sequence
  depends only on the key *prefix* (c_0..c_j) — lexicographic sort is
  refined, not reordered, by trailing key columns — so targets sharing a
  prefix share both the sort permutation and, for ORD-IND methods (which
  ignore order entirely), the per-column byte counts.

Concretely, per (table, f) group the engine:

1. collects the distinct (method, prefix, rows-per-page) jobs of all
   targets (ORD-IND jobs collapse to (method, column));
2. materializes one permutation per *maximal* prefix and reuses it for
   every shorter prefix it extends: the prefix's dense column ranks are
   folded into one int64 key, re-densified where the next column would
   pass 63 bits and no longer folded once the key is all-distinct, and
   the keys are argsorted stably in one call (`_prefix_permutations`);
3. stacks the permuted columns into (ntargets, nrows) matrices grouped by
   (method, rows-per-page) and sizes them with the `*_bytes_batch` kernels
   of `repro.core.compression` (NumPy, or — with `backend="jax"` — the
   bit-identical Pallas segment-reduce kernels in
   repro.kernels.codec_bytes);
4. assembles per-target compressed bytes, applies the same bias
   correction (`errors.samplecf_bias`) and full-table scaling as
   `sample_cf`, and returns `SizeEstimate`s that match the scalar path
   float-for-float.

Exactness (asserted in tests/test_estimation_engine.py on both backends):
per-column integer byte counts equal the scalar kernels', so `cf`,
`est_bytes` and `cost_pages` are byte-identical.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import compression, distinct, errors, tracing
from .backend import resolve as _resolve
from .relation import IndexDef, Table, rows_per_page, uncompressed_pages
from .samplecf import SampleManager, SizeEstimate

# (cols, method) — method None means "uncompressed" (CF = 1.0)
TargetSpec = Tuple[Tuple[str, ...], Optional[str]]


_permute_counters = {"prefixes": 0, "densify": 0, "cut_short": 0,
                     "columns_skipped": 0, "scattered": 0}


def permute_counters() -> Dict[str, int]:
    """Process-wide counters of `_prefix_permutations`' rank fold: maximal
    prefixes sorted (`prefixes`), re-densify passes (`densify`), prefixes
    stopped at an all-distinct key (`cut_short`), the key columns those
    never folded (`columns_skipped`), and permutations taken by a scatter
    instead of a sort (`scattered`)."""
    return dict(_permute_counters)


@tracing.traced("samplecf.permute")
def _prefix_permutations(sample: Table,
                         prefixes: Sequence[Tuple[str, ...]]
                         ) -> Dict[Tuple[str, ...], np.ndarray]:
    """One sort order per *maximal* prefix; shorter prefixes reuse it.

    Valid because a lexicographic sort by (c_0..c_k) orders the (c_0..c_j)
    tuples, j <= k, exactly as a sort by (c_0..c_j) does — trailing key
    columns only permute rows *within* groups of equal (c_0..c_j) values,
    where c_j is constant.

    Each maximal prefix is folded into one int64 key, order-isomorphic to
    its row tuples: the key starts at the first column's dense rank and
    shifts in each further column's rank (`key << bits | rank`).  When the
    next column would overflow 63 bits, the key is re-densified
    (`np.unique(return_inverse=True)`, at most bit_length(nrows - 1) bits)
    and the fold goes on.  Once the key is all-distinct — a dense rank, or
    a fold that took in an all-distinct column, with as many distinct
    values as rows — the later columns cannot reorder it and are never
    folded.  Maximal prefixes are visited in sorted order and the keys of
    the current one's heads are kept, so prefixes that share leading
    columns fold them once.  A stable row-wise argsort of the keys, one
    call for all of them, gives each permutation (ties broken by row
    index, as a lexicographic sort does); a key that ended dense and
    all-distinct is already each row's sorted position and is scattered
    instead (`perm[key] = arange(n)`).
    """
    n = sample.nrows
    uniq = set(prefixes)
    parents = {p[:-1] for p in uniq if len(p) > 1}
    maximal = sorted(p for p in uniq if p not in parents)

    # per column: (dense rank, its bits, all-distinct)
    cols: Dict[str, Tuple[np.ndarray, int, bool]] = {}

    def rank_of(c: str) -> Tuple[np.ndarray, int, bool]:
        got = cols.get(c)
        if got is None:
            u, inv = np.unique(sample.values[c], return_inverse=True)
            got = cols[c] = (inv.astype(np.int64, copy=False),
                             max(int(u.size - 1).bit_length(), 1),
                             u.size == n)
        return got

    # heads[j]: (key, bits, all-distinct, dense) of the current maximal
    # prefix's first j + 1 columns
    heads: List[Tuple[np.ndarray, int, bool, bool]] = []
    prev: Tuple[str, ...] = ()
    # the final keys, once each: prefixes cut short at a shared head
    # share its key and so its permutation
    finals: Dict[int, Tuple[np.ndarray, bool]] = {}
    final_of: Dict[Tuple[str, ...], int] = {}
    for p in maximal:
        shared = 0
        while shared < len(heads) and prev[shared] == p[shared]:
            shared += 1
        del heads[shared:]
        prev = p
        if not heads:
            r, b, distinct = rank_of(p[0])
            heads.append((r, b, distinct, True))
        while len(heads) < len(p) and not heads[-1][2]:
            key, b = heads[-1][:2]
            r, rb, distinct = rank_of(p[len(heads)])
            if b + rb > 63:
                u, inv = np.unique(key, return_inverse=True)
                _permute_counters["densify"] += 1
                key, b = inv.astype(np.int64, copy=False), \
                    int(u.size - 1).bit_length()
                # the dense head serves every later prefix that shares it
                heads[-1] = (key, b, u.size == n, True)
                if u.size == n:
                    continue
            heads.append(((key << rb) | r, b + rb, distinct, False))
        if len(heads) < len(p):
            _permute_counters["cut_short"] += 1
            _permute_counters["columns_skipped"] += len(p) - len(heads)
        key, _, distinct, dense = heads[-1]
        finals.setdefault(id(key), (key, distinct and dense))
        final_of[p] = id(key)
    _permute_counters["prefixes"] += len(maximal)

    perm_of: Dict[int, np.ndarray] = {}
    sort = [k for k, (_, scatter) in finals.items() if not scatter]
    if sort:
        perms = np.argsort(np.stack([finals[k][0] for k in sort]), axis=1,
                           kind="stable")
        perm_of.update(zip(sort, perms))
    for k, (key, scatter) in finals.items():
        if scatter:
            perm = perm_of[k] = np.empty(n, dtype=np.int64)
            perm[key] = np.arange(n)
    out: Dict[Tuple[str, ...], np.ndarray] = {}
    for p, k in final_of.items():
        out[p] = perm_of[k]
        _permute_counters["scattered"] += finals[k][1]
    # every needed non-maximal prefix is an ancestor of some maximal one
    for p in maximal:
        perm = out[p]
        for j in range(len(p) - 1, 0, -1):
            anc = p[:j]
            if anc in uniq and anc not in out:
                out[anc] = perm
    return out


@tracing.traced("estimate.samplecf")
def batched_sample_cf(table: Table, sample: Table,
                      specs: Sequence[TargetSpec], f: float,
                      bias_correct: bool = True,
                      backend: str = "numpy") -> List[SizeEstimate]:
    """SampleCF for every (cols, method) spec on one shared sample.

    `table` provides column widths and the full-index row count used to
    scale CF back up (§2.2); `sample` is the (table, f) sample the indexes
    are built on.  Returns estimates aligned with `specs`, byte-identical
    to calling `sample_cf` per target.
    """
    n = sample.nrows
    widths_of = {c.name: table.col_by_name[c.name].width
                 for c in sample.columns}

    def rpp_key(rpp: int) -> int:
        # Any rows-per-page >= n yields a single page holding all n rows,
        # and single-page sizes are rpp-independent (padding repeats the
        # last value, which adds no distinct values, runs, or min/max
        # movement) — so such jobs collapse into one per (method, prefix).
        return rpp if 0 < rpp < n else max(n, 1)

    # ---- collect the distinct sizing jobs across all targets ----
    ordind_jobs = set()           # (method, col)
    orddep_jobs = set()           # (method, prefix, rpp_key)
    gdict_jobs = set()            # col — AE-priced at full cardinality
    for cols, method in specs:
        if method is None:
            continue
        rpp = rpp_key(rows_per_page(sum(widths_of[c] for c in cols)))
        order_dep = compression.METHODS[method].order_dependent
        for j, c in enumerate(cols):
            if method == "GDICT":
                gdict_jobs.add(c)
            elif order_dep:
                orddep_jobs.add((method, cols[:j + 1], rpp))
            else:
                ordind_jobs.add((method, c))

    # ---- closed forms for single-page order-dependent jobs ----
    # When the whole sample fits in one page, LDICT's page dictionary sees
    # the column's full multiset (ndv) and PREFIX sees its global min/max —
    # both independent of the sort order — so these jobs reduce to O(1)
    # arithmetic on per-column stats the sample Table already caches.
    col_bytes: Dict[Tuple, int] = {}
    kernel_jobs = set()
    single = max(n, 1)
    for job in orddep_jobs:
        method, prefix, rpp = job
        c = prefix[-1]
        w = widths_of[c]
        cap = n * w + compression.PAGE_META
        if rpp == single and method == "LDICT":
            ndv = sample.ndv([c])
            ptr = int(compression._ptr_bytes(ndv))
            col_bytes[job] = min(ndv * w + n * ptr + compression.PAGE_META,
                                 cap)
        elif rpp == single and method == "PREFIX":
            mn, mx = sample.minmax(c)
            # uint64 semantics, like the kernel's significant_bytes cast
            # (Table enforces non-negative values, so this is defensive)
            xor = (mn ^ mx) & 0xFFFFFFFFFFFFFFFF
            diff_bytes = (xor.bit_length() + 7) // 8  # significant_bytes
            common = max(w - diff_bytes, 0)
            col_bytes[job] = min(
                common + n * (1 + w - common) + compression.PAGE_META, cap)
        else:
            kernel_jobs.add(job)

    # ---- GDICT: App. B Adaptive-Estimator pricing (samplecf parity) ----
    # The sample's dictionary is nearly all-distinct at small f, so GDICT
    # sizes are not CF-scaled; the shared `gdict_estimated_col_bytes`
    # estimates full-table NDV per column and prices the full index
    # directly — bit-identical to the scalar sample_cf GDICT path (the
    # estimator only depends on the sample's value multiset).
    gdict_bytes: Dict[str, float] = {
        c: distinct.gdict_estimated_col_bytes(sample.values[c],
                                              widths_of[c], table.nrows)
        for c in gdict_jobs}

    perms = _prefix_permutations(
        sample, [p for (_, p, _) in kernel_jobs]) if kernel_jobs else {}

    # ---- grouped kernel calls ----
    by_method: Dict[str, List[Tuple[str, ...]]] = {}
    for method, c in ordind_jobs:
        by_method.setdefault(method, []).append(c)
    for method, jcols in by_method.items():
        # ORD-IND sizes ignore row order: use raw sample order
        mat = np.stack([sample.values[c] for c in jcols])
        w = np.array([widths_of[c] for c in jcols], dtype=np.int64)
        got = compression.batched_bytes(method, mat, w, rows_per_page(1),
                                        backend=backend)
        for c, b in zip(jcols, got):
            col_bytes[(method, c)] = int(b)

    by_group: Dict[Tuple[str, int], List[Tuple[str, ...]]] = {}
    for method, prefix, rpp in kernel_jobs:
        by_group.setdefault((method, rpp), []).append(prefix)
    for (method, rpp), prefixes in by_group.items():
        mat = np.stack([sample.values[p[-1]][perms[p]] for p in prefixes])
        w = np.array([widths_of[p[-1]] for p in prefixes], dtype=np.int64)
        got = compression.batched_bytes(method, mat, w, rpp, backend=backend)
        for p, b in zip(prefixes, got):
            col_bytes[(method, p, rpp)] = int(b)

    # ---- per-target assembly (same float ops, same order, as sample_cf) --
    colset_cache: Dict[Tuple[str, ...], Tuple] = {}

    def colset_consts(cols: Tuple[str, ...]) -> Tuple:
        got = colset_cache.get(cols)
        if got is None:
            widths = [widths_of[c] for c in cols]
            got = colset_cache[cols] = (
                rpp_key(rows_per_page(sum(widths))),
                compression.uncompressed_payload_bytes(n, widths),
                compression.uncompressed_payload_bytes(table.nrows, widths),
                float(uncompressed_pages(n, widths)))
        return got

    out: List[SizeEstimate] = []
    for cols, method in specs:
        rpp, s, full_bytes, cost = colset_consts(tuple(cols))
        if method is None or n == 0 or s == 0:
            cf = 1.0
        elif method == "GDICT":
            # full-cardinality AE pricing (same op order as sample_cf)
            sc = table.nrows * compression.ROW_OVERHEAD
            for c in cols:
                sc = sc + gdict_bytes[c]
            cf = sc / full_bytes
            if bias_correct:
                cf = min(cf / errors.samplecf_bias(method, f), 1.0)
        else:
            order_dep = compression.METHODS[method].order_dependent
            sc = n * compression.ROW_OVERHEAD
            for j, c in enumerate(cols):
                sc += col_bytes[(method, cols[:j + 1], rpp)] if order_dep \
                    else col_bytes[(method, c)]
            cf = sc / s
            if bias_correct:
                cf = min(cf / errors.samplecf_bias(method, f), 1.0)
        out.append(SizeEstimate(
            index=IndexDef(table.name, tuple(cols), method),
            est_bytes=cf * full_bytes, method="samplecf",
            cost_pages=cost, cf=cf))
    return out


class EstimationEngine:
    """Batched SampleCF over a schema and an amortized sample store.

    Accepts any target objects carrying `.table`, `.cols` and `.method`
    (`estimation_graph.NodeKey` in the advisor pipeline) and estimates all
    of them per (table, f) group in grouped kernel calls.
    """

    def __init__(self, tables: Dict[str, Table],
                 manager: Optional[SampleManager] = None,
                 backend: str = "numpy", seed: int = 0, faults=None):
        self.tables = dict(tables)
        self.manager = manager if manager is not None else \
            SampleManager(self.tables, seed=seed)
        self.backend = _resolve(backend)
        # optional faults.FaultInjector; site "estimation" fires a
        # transient FaultError before any sampling work happens, so a
        # faulted batch is cleanly retryable
        self.faults = faults
        self.batch_calls = 0        # per-(table, f) group batches run
        self.targets_estimated = 0  # total targets sized through the engine
        # codec stacks the jax kernels sent to NumPy for leaving their
        # int32 exactness envelope
        self.envelope_reroutes = 0
        # this engine's share of permute_counters(): the prefix-sort fold
        self.permute = dict.fromkeys(_permute_counters, 0)

    def stats(self) -> Dict[str, int]:
        return {"batch_calls": self.batch_calls,
                "targets_estimated": self.targets_estimated,
                "envelope_reroutes": self.envelope_reroutes,
                **{f"permute_{k}": v for k, v in self.permute.items()}}

    def warm_up(self, f: float, methods: Sequence[str]) -> int:
        """Run once each codec program a batch at fraction `f` can use on
        this backend, so that no batch lowers one: for every table, its
        sample's rows and the page lengths of every key width the table
        allows (`codec_bytes.programs`).  Returns the launches; 0 on the
        NumPy backend."""
        if self.backend != "jax":
            return 0
        from ..kernels import codec_bytes
        launches = []
        for name, table in self.tables.items():
            widths = [c.width for c in table.columns]
            launches += codec_bytes.programs(
                self.manager.sample_rows(name, f), methods,
                range(rows_per_page(sum(widths)),
                      rows_per_page(min(widths)) + 1))
        return codec_bytes.warm_up(sorted(set(launches)))

    def estimate_batch(self, targets: Sequence, f: float,
                       bias_correct: bool = True) -> Dict:
        """SizeEstimate for every target, keyed by the target objects."""
        if self.faults is not None:
            self.faults.check("estimation", f"estimate_batch of "
                              f"{len(targets)} targets at f={f}")
        by_table: Dict[str, List] = {}
        for t in targets:
            by_table.setdefault(t.table, []).append(t)
        out: Dict = {}
        reroutes = _codec_reroutes(self.backend)
        permute = permute_counters()
        for tname, ts in by_table.items():
            sample = self.manager.get_sample(tname, f)
            ests = batched_sample_cf(
                self.tables[tname], sample, [(t.cols, t.method) for t in ts],
                f, bias_correct=bias_correct, backend=self.backend)
            out.update(zip(ts, ests))
            self.batch_calls += 1
            self.targets_estimated += len(ts)
        self.envelope_reroutes += _codec_reroutes(self.backend) - reroutes
        for k, v in permute_counters().items():
            self.permute[k] += v - permute[k]
        return out


def _codec_reroutes(backend: str) -> int:
    """Process-wide codec envelope reroutes of the jax kernels."""
    if backend != "jax":
        return 0
    from ..kernels import codec_bytes
    return codec_bytes.counters()["envelope_reroutes"]
