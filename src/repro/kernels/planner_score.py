"""Pallas (node x f) scoring kernels for the batched §5.2 planner.

Two kernels behind `PlannerEngine(backend="jax")`:

* `prob_within` — the accuracy-probability stage
  ``P(1/(1+e) <= X <= 1+e)`` over (mean, std) stacks, mirroring
  `errors.prob_within_batch` (same std<=1e-12 indicator branch, same phi
  evaluation order) in float32 on the VPU.

* `fused_score` — the whole candidate-scoring step of one §5.2 target
  fused into one kernel: the sequential Goodman fold over the (candidate,
  child, f) RV stack, continued with the deduction-error factor, the
  composed std, the masked accuracy probability, and the lines-6-9 winner
  selection (first-argmax of p over eligible candidates, first-argmin of
  the extra sampling cost) per fraction.  `fused_score_batch` puts the
  candidate rows of several targets through the same kernel in one
  launch; rows are independent, so each target's values are bitwise
  those of its own launch.  `programs` is the fixed set of padded
  shapes, `warm_up` runs it.

Consistency contract (this is what keeps replay and session-vs-fresh
plan equality exact under the jax backend): both kernels evaluate the
probability through the SAME `_prob_expr` op sequence, so a probability
recomputed later from a stored (mean, std) pair — planner buf values are
float32-exact once written — is bit-identical to the fused kernel's
in-line value.  The engine consumes the fused kernel's cm/cs/p and keeps
winner selection on the float64 side (p is float32-exact so the argmax
agrees; the lines-8-9 extra-cost argmin stays on the engine's float64
sampling costs, which the in-kernel float32 argmin mirrors except on
sub-ulp ties).  The kernels are NOT bit-parity with the float64 NumPy
backend (a different erf and float32 arithmetic); the NumPy backend
remains the parity reference against the scalar planner.

Parity suite: tests/test_pallas_parity.py asserts `prob_within` against
`errors.prob_within_batch` within float32 tolerance (exactly on the
indicator branch) and asserts `fused_score`'s staged outputs (cm/cs/p)
and winners against a NumPy re-expression of the same fold.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import tracing
from ..core.backend import pallas_interpret

_LANES = 128
_SQRT2_F32 = np.float32(math.sqrt(2.0))
_BIG = np.int32(2 ** 31 - 1)  # "no winner" sentinel for the argmin outputs

_counters = {"prob_calls": 0, "fused_calls": 0, "h2d_bytes": 0,
             "d2h_bytes": 0}


def counters() -> dict:
    """Process-wide kernel launch counts, and the bytes of the arrays the
    launches sent to the device (`h2d_bytes`) and read back
    (`d2h_bytes`)."""
    return dict(_counters)


# Rational float32 erf (the Eigen/XLA f32 approximation): Mosaic has no
# erf lowering, but lowers this clamp + two Horner polynomials + divide.
# |erf_f32(x) - erf(x)| < 5e-7 over a dense grid; beyond |x| = 4 erf is
# +-1 in float32.
_ERF_ALPHA = tuple(np.float32(c) for c in (
    -2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
    -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
    -1.60960333262415e-02))
_ERF_BETA = tuple(np.float32(c) for c in (
    -1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
    -7.37332916720468e-03, -1.42647390514189e-02))


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def erf_f32(x):
    """float32 erf from ops every backend lowers (see _ERF_ALPHA)."""
    x = jnp.clip(x, jnp.float32(-4.0), jnp.float32(4.0))
    x2 = x * x
    return x * _horner(x2, _ERF_ALPHA) / _horner(x2, _ERF_BETA)


def _prob_expr(cm, cs, e: float):
    """float32 accuracy probability, one op sequence shared by BOTH kernels
    (the engine's replay consistency depends on this being identical)."""
    lo = jnp.float32(1.0 / (1.0 + e))
    hi = jnp.float32(1.0 + e)
    small = cs <= jnp.float32(1e-12)
    s = jnp.where(small, jnp.float32(1.0), cs)
    phi_hi = jnp.float32(0.5) * (jnp.float32(1.0)
                                 + erf_f32((hi - cm) / s / _SQRT2_F32))
    phi_lo = jnp.float32(0.5) * (jnp.float32(1.0)
                                 + erf_f32((lo - cm) / s / _SQRT2_F32))
    ind = ((cm >= lo) & (cm <= hi)).astype(jnp.float32)
    return jnp.where(small, ind, phi_hi - phi_lo)


def _compose_expr(m, s, dm, vt, mq):
    """Sequential Goodman fold over the child axis of (nc, K, nf) stacks,
    continued with the (nc, 1) deduction-error factors — the float32 twin
    of errors.goodman_fold + the engine's deduction continuation.  A
    (mean=1, std=0) EXACT pad is the exact multiplicative identity in
    float32 too, so folds of different padded K agree bitwise."""
    k = m.shape[1]
    e_prod = m[:, 0, :]
    v_term = s[:, 0, :] * s[:, 0, :] + e_prod * e_prod
    e2_term = e_prod * e_prod
    for kk in range(1, k):
        mk = m[:, kk, :]
        sk = s[:, kk, :]
        msq = mk * mk
        e_prod = e_prod * mk
        v_term = v_term * (sk * sk + msq)
        e2_term = e2_term * msq
    cm = e_prod * dm
    v = v_term * vt
    e2 = e2_term * mq
    cs = jnp.sqrt(jnp.maximum(v - e2, jnp.float32(0.0)))
    return cm, cs


# ---------------------------------------------------------------------------
# prob_within: 1-D probability stage
# ---------------------------------------------------------------------------

def _prob_kernel(m_ref, s_ref, o_ref, *, e: float):
    o_ref[...] = _prob_expr(m_ref[...], s_ref[...], e)


@functools.partial(jax.jit, static_argnames=("e", "interpret"))
def _prob_call(m, s, *, e: float, interpret: bool):
    return pl.pallas_call(
        functools.partial(_prob_kernel, e=e),
        grid=(1,),
        in_specs=[pl.BlockSpec(m.shape, lambda i: (0, 0)),
                  pl.BlockSpec(m.shape, lambda i: (0, 0))],
        out_specs=[pl.BlockSpec(m.shape, lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(m.shape, jnp.float32)],
        interpret=interpret,
        name="planner_prob",
    )(m, s)[0]


@tracing.traced("kernel.planner")
def prob_within(means: np.ndarray, stds: np.ndarray, e: float) -> np.ndarray:
    """Pallas twin of errors.prob_within_batch (float32).  Accepts any
    shape; pads to pow2 lane multiples to bound the compiled-shape count
    (same idiom as the retired jitted-erf backend)."""
    means = np.asarray(means, dtype=np.float64)
    stds = np.asarray(stds, dtype=np.float64)
    n = means.size
    if n == 0:
        return np.zeros(means.shape)
    n_pad = max(_LANES, 1 << int(n - 1).bit_length())
    mp = np.ones((1, n_pad), dtype=np.float32)
    sp = np.zeros((1, n_pad), dtype=np.float32)
    mp[0, :n] = means.ravel()
    sp[0, :n] = stds.ravel()
    out = _prob_call(jnp.asarray(mp), jnp.asarray(sp), e=float(e),
                     interpret=pallas_interpret())
    _counters["prob_calls"] += 1
    _counters["h2d_bytes"] += mp.nbytes + sp.nbytes
    _counters["d2h_bytes"] += out.nbytes
    return np.asarray(out, dtype=np.float64)[0, :n].reshape(means.shape)


# ---------------------------------------------------------------------------
# fused_score: compose + prob + winner selection for one target record
# ---------------------------------------------------------------------------

def _fused_kernel(m_ref, s_ref, dm_ref, vt_ref, mq_ref, m67_ref, p9_ref,
                  ex_ref, cm_ref, cs_ref, p_ref, w6_ref, w9_ref,
                  *, k: int, nf: int, e: float, q: float):
    nc = m_ref.shape[0]
    m = m_ref[...].reshape(nc, k, nf)
    s = s_ref[...].reshape(nc, k, nf)
    cm, cs = _compose_expr(m, s, dm_ref[...], vt_ref[...], mq_ref[...])
    m67 = m67_ref[...] != 0
    p9 = p9_ref[...] != 0
    p = jnp.where(m67 | p9, _prob_expr(cm, cs, e), jnp.float32(0.0))
    sat = p >= jnp.float32(q)
    iota = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    # lines 6-7: first argmax of p over eligible (enabled & satisfying)
    elig = m67 & sat
    pe = jnp.where(elig, p, jnp.float32(-1.0))
    best = jnp.max(pe, axis=0, keepdims=True)
    w6 = jnp.min(jnp.where(elig & (pe == best), iota, _BIG), axis=0,
                 keepdims=True)
    # lines 8-9: first argmin of extra sampling cost where no line-6 winner
    has6 = jnp.any(elig, axis=0, keepdims=True)
    ok9 = p9 & sat & ~has6
    xe = jnp.where(ok9, ex_ref[...], jnp.float32(np.inf))
    bx = jnp.min(xe, axis=0, keepdims=True)
    w9 = jnp.min(jnp.where(ok9 & (xe == bx), iota, _BIG), axis=0,
                 keepdims=True)
    cm_ref[...] = cm
    cs_ref[...] = cs
    p_ref[...] = p
    w6_ref[...] = w6
    w9_ref[...] = w9


@functools.partial(jax.jit, static_argnames=("k", "e", "q", "interpret"))
def _fused_call(m, s, dm, vt, mq, m67, p9, ex, *, k: int, e: float,
                q: float, interpret: bool):
    nc, knf = m.shape
    nf = knf // k
    full = lambda i: (0, 0)  # noqa: E731 - single-block grid
    spec = lambda shape: pl.BlockSpec(shape, full)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fused_kernel, k=k, nf=nf, e=e, q=q),
        grid=(1,),
        in_specs=[spec(m.shape), spec(s.shape), spec(dm.shape),
                  spec(vt.shape), spec(mq.shape), spec(m67.shape),
                  spec(p9.shape), spec(ex.shape)],
        out_specs=[spec((nc, nf)), spec((nc, nf)), spec((nc, nf)),
                   spec((1, nf)), spec((1, nf))],
        out_shape=[jax.ShapeDtypeStruct((nc, nf), jnp.float32),
                   jax.ShapeDtypeStruct((nc, nf), jnp.float32),
                   jax.ShapeDtypeStruct((nc, nf), jnp.float32),
                   jax.ShapeDtypeStruct((1, nf), jnp.int32),
                   jax.ShapeDtypeStruct((1, nf), jnp.int32)],
        interpret=interpret,
        name="planner_fused_score",
    )(m, s, dm, vt, mq, m67, p9, ex)


# the launches `warm_up` runs, and the walk's cap on a batch: candidate
# rows, children per candidate, and padded rows x padded children.  A
# (rows, children x 128 lanes) float32 stack of 4096 rows x children is
# what one v5e kernel's scoped VMEM holds with m and s double-buffered
# (128 x 64 runs out).  TPC-H SF1 records reach 19 candidates and 30
# children, and a batch of them 70 rows; a larger record is a launch of
# its own and lowers its program when it first comes.
MAX_CANDIDATES = 128
MAX_CHILDREN = 64
MAX_CELLS = 4096


def _buckets(rows: int, k: int) -> Tuple[int, int]:
    """Padded (candidate, child) axes: powers of two keep the programs a
    small fixed set; EXACT (mean=1, std=0) child pads are the fold's
    exact identity, and pad candidates are never eligible."""
    return max(8, 1 << (rows - 1).bit_length()), 1 << (k - 1).bit_length()


def fits(rows: int, k: int) -> bool:
    """Whether a launch of `rows` candidates of up to `k` children is one
    of the programs `warm_up` runs."""
    nc_pad, k_pad = _buckets(rows, k)
    return (nc_pad <= MAX_CANDIDATES and k_pad <= MAX_CHILDREN
            and nc_pad * k_pad <= MAX_CELLS)


def programs() -> List[Tuple[int, int]]:
    """The padded (candidates, children) of every program `warm_up` runs:
    each power-of-two pair that `fits`."""
    out = []
    nc = 8
    while nc <= MAX_CANDIDATES:
        k = 1
        while fits(nc, k):
            out.append((nc, k))
            k *= 2
        nc *= 2
    return out


Record = Tuple[np.ndarray, ...]   # (m, s, dm, vt, mq, mask67, pre9, extra)


def _launch(records: Sequence[Record], e: float, q: float) -> tuple:
    """Pack records' candidate rows one after another into one padded
    stack, EXACT-padded to the widest record's children, and launch the
    fused kernel once.  Returns the device outputs and the real rows."""
    nf = records[0][5].shape[1]
    rows = sum(r[0].shape[0] for r in records)
    nc_pad, k_pad = _buckets(rows, max(r[0].shape[1] for r in records))
    nf_pad = -(-nf // _LANES) * _LANES
    mp = np.ones((nc_pad, k_pad, nf_pad), dtype=np.float32)
    sp = np.zeros((nc_pad, k_pad, nf_pad), dtype=np.float32)
    dmp, vtp, mqp = (np.ones((nc_pad, 1), dtype=np.float32)
                     for _ in range(3))
    m67p, p9p = (np.zeros((nc_pad, nf_pad), dtype=np.int32)
                 for _ in range(2))
    exp_ = np.zeros((nc_pad, nf_pad), dtype=np.float32)
    r0 = 0
    for m, s, dm, vt, mq, mask67, pre9, extra in records:
        nc, k, _ = m.shape
        r1 = r0 + nc
        mp[r0:r1, :k, :nf] = m
        sp[r0:r1, :k, :nf] = s
        dmp[r0:r1] = dm
        vtp[r0:r1] = vt
        mqp[r0:r1] = mq
        m67p[r0:r1, :nf] = mask67
        if pre9 is not None:
            p9p[r0:r1, :nf] = pre9
        if extra is not None:
            exp_[r0:r1, :nf] = extra
        r0 = r1
    args = (mp.reshape(nc_pad, k_pad * nf_pad),
            sp.reshape(nc_pad, k_pad * nf_pad), dmp, vtp, mqp, m67p, p9p,
            exp_)
    outs = _fused_call(*jax.device_put(args), k=k_pad, e=float(e),
                       q=float(q), interpret=pallas_interpret())
    _counters["fused_calls"] += 1
    _counters["h2d_bytes"] += sum(a.nbytes for a in args)
    return outs, rows


@tracing.traced("kernel.planner")
def fused_score(m: np.ndarray, s: np.ndarray, dm: np.ndarray,
                vt: np.ndarray, mq: np.ndarray, mask67: np.ndarray,
                pre9, extra, e: float, q: float):
    """One fused pass over a target's (nc, K, nf) candidate stack.

    m/s are child RV means/stds (EXACT-padded along K), dm/vt/mq the
    (nc, 1) deduction-error continuation factors, mask67/pre9 the
    lines-6-7 / lines-8-9 eligibility masks, extra the summed sampling
    cost of unknown children (lines 8-9 tie-break axis).  Returns
    (cm, cs, p, w6, w9): composed mean/std, masked probability — all
    float32 values in float64 arrays — and the per-f winner indices
    (int64; meaningless where the respective mask column is empty).
    """
    nc, _, nf = m.shape
    outs, _ = _launch([(m, s, dm, vt, mq, mask67, pre9, extra)], e, q)
    outs = jax.device_get(outs)
    _counters["d2h_bytes"] += sum(o.nbytes for o in outs)
    cm, cs, p, w6, w9 = outs
    return (np.asarray(cm, dtype=np.float64)[:nc, :nf],
            np.asarray(cs, dtype=np.float64)[:nc, :nf],
            np.asarray(p, dtype=np.float64)[:nc, :nf],
            np.asarray(w6, dtype=np.int64)[0, :nf],
            np.asarray(w9, dtype=np.int64)[0, :nf])


@tracing.traced("kernel.planner")
def fused_score_batch(records: Sequence[Record], e: float,
                      q: float) -> List[Tuple[np.ndarray, ...]]:
    """`fused_score` of several records in one launch and one read-back.

    Each record is `fused_score`'s (m, s, dm, vt, mq, mask67, pre9,
    extra).  Every candidate row of the kernel is computed from its own
    inputs alone, and EXACT child pads are the fold's identity, so each
    record's (cm, cs, p) is bitwise what `fused_score` returns for it.
    The in-kernel winners span the whole launch and are not read back:
    the caller selects each record's winners from its own rows."""
    nf = records[0][5].shape[1]
    outs, rows = _launch(records, e, q)
    cm, cs, p = (np.asarray(o, dtype=np.float64)[:rows, :nf]
                 for o in jax.device_get(outs[:3]))
    _counters["d2h_bytes"] += sum(o.nbytes for o in outs[:3])
    res = []
    r0 = 0
    for rec in records:
        r1 = r0 + rec[0].shape[0]
        res.append((cm[r0:r1], cs[r0:r1], p[r0:r1]))
        r0 = r1
    return res


# accuracy targets whose programs this process has run: compiled
# programs live in jax's process-wide cache, so this set does too
_warmed: set = set()


def warm_up(e: float, q: float) -> int:
    """Run once each `fused_score` program of `programs()` at accuracy
    (e, q).  Returns the launches."""
    shapes = programs()
    for nc, k in shapes:
        ones, zeros = np.ones((nc, 1)), np.zeros((nc, 1), dtype=bool)
        fused_score(np.ones((nc, k, 1)), np.zeros((nc, k, 1)), ones, ones,
                    ones, zeros, None, None, e, q)
    _warmed.add((float(e), float(q)))
    return len(shapes)


def warm_once(e: float, q: float) -> int:
    """`warm_up(e, q)` the first time a compiling process asks for it,
    so that no scoring launch whose shape depends on the walk's decisions
    lowers a program later.  Interpret mode (the CPU) compiles nothing
    worth keeping out of a timed window and skips it.  Returns the
    launches."""
    key = (float(e), float(q))
    if key in _warmed or pallas_interpret():
        return 0
    _warmed.add(key)
    return warm_up(e, q)
