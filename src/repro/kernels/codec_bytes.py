"""Pallas segment-reduce kernels for the batched codec-size estimators.

These kernels accelerate `repro.core.compression.batched_bytes` — the
(targets x rows) column stacks that SampleCF and the estimation engine feed
through the five codec size formulas (NS / GDICT / LDICT / PREFIX / RLE).

Layout.  Every method reduces *segments*: a whole target row for NS and
GDICT, one page of a target row for LDICT, PREFIX and RLE.  The wrapper
lays the segments out one per kernel row and edge-pads each row to a
multiple of 128 lanes with the segment's last value (after the sort, for
GDICT and LDICT), so a padding lane adds no distinct value, no run and no
min/max movement; NS masks its padding with the segment's row count.
Pages are gathered `page_bucket(page)` lanes wide with the page length a
traced value, and a call's targets go in launches of `CHUNK` rows, so
the programs a stack of n sample rows can lower are a small fixed set
(`programs`, `warm_up`).  The grid is (row tiles, column tiles): a
block holds at most `_BLOCK_ELEMS` values per plane at any sample size,
and per-row accumulators carry the partial count, the running min/max
and the previous tile's last value across column tiles, so a change that
falls on a tile boundary is counted exactly once.  Paged methods then
sum their per-page bytes per target.

int32-safe arithmetic (no x64, no unsigned reductions):

* Values are split into two uint32 planes ``hi = v >> 32``, ``lo = v & M32``
  of the uint64 view of the input, each in its order-preserving signed view
  ``s(u) = (u ^ 0x80000000)`` bitcast to int32.  The split happens on the
  device: the (m, n) int64 stack travels as its own bytes, an (m, 2n) int32
  view with ``lo`` on the even lanes and ``hi`` on the odd ones (the host
  is little-endian), and `_codec_call` takes the two lane-strided halves
  and XORs each with the bias.  The split is a bijection and the view keeps
  equality and order, so every primitive the codecs need factors exactly
  through the two views:
  - equality / adjacent-difference: ``a == b  <=>  a_hi == b_hi and
    a_lo == b_lo`` (GDICT/LDICT ndv counts, RLE run counts);
  - order: lexicographic (hi, lo) order equals uint64 order, so
    ``jax.lax.sort((hi, lo), num_keys=2)`` sorts exactly like the NumPy
    reference's int64 sort for non-negative inputs, and the PREFIX page
    min/max decompose as ``mn_hi = min(hi)``,
    ``mn_lo = min(lo where hi == mn_hi)`` (dually for max);
  - xor: ``s(a) ^ s(b) == a ^ b`` — the bias cancels;
  - significant_bytes: ``sig(v) = 4 + sig32(hi)`` if ``hi != 0`` else
    ``sig32(lo)`` with ``sig32(u) = 1 + [u>=2^8] + [u>=2^16] + [u>=2^24]``,
    each threshold compared in the signed view.
* All byte-count arithmetic is then small-integer: with widths <= 8 every
  per-row/per-page term is <= ``rows * (width + 3) + PAGE_META``, so the
  final int32 accumulators stay below 2^31 whenever ``n <= 2^25`` rows.
  Inputs outside the proven envelope (negative values — the signed PREFIX
  min/max would diverge — more rows, or wider columns) are routed to the
  NumPy reference kernels and counted in ``counters()["envelope_reroutes"]``,
  so `batched_codec_bytes` is exact for every input.

Parity contract: bit-identical to `compression.BATCH_KERNELS[method]` —
asserted by tests/test_pallas_parity.py; tests/test_tpu_compile.py compiles
the kernels for a TPU v5e at SampleCF's real sample shapes.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import tracing
from ..core.backend import pallas_interpret

# mirror repro.core.compression.PAGE_META / _ptr_bytes thresholds; the
# NumPy references are imported lazily in the reroute path
_PAGE_META = 16
_LANES = 128
_SUBLANES = 8
_IMIN = np.int32(-(2 ** 31))
_IMAX = np.int32(2 ** 31 - 1)

# block geometry: at most _BLOCK_ELEMS values per plane block (512 KiB of
# int32) and _MAX_TILE_C lanes per column tile, whatever the sample size
_BLOCK_ELEMS = 1 << 17
_MAX_TILE_C = 2048

# envelope of the int32 exactness proof (see module docstring)
_MAX_ROWS = 1 << 25
_MAX_WIDTH = 8

ORD_IND_METHODS = ("NS", "GDICT")
ORD_DEP_METHODS = ("LDICT", "PREFIX", "RLE")

_counters = {"kernel_calls": 0, "envelope_reroutes": 0, "h2d_bytes": 0,
             "d2h_bytes": 0}


def counters() -> dict:
    """Process-wide call counters: Pallas launches, stacks rerouted to
    NumPy for leaving the int32 envelope, and the bytes of the arrays the
    launches sent to the device (`h2d_bytes`) and read back
    (`d2h_bytes`)."""
    return dict(_counters)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _signed(k: int):
    """Signed view of the uint32 constant k."""
    return jnp.int32(k - (1 << 31))


def _sig32(s):
    """Significant bytes (1..4) of a uint32 plane given in its signed view."""
    return (jnp.int32(1)
            + (s >= _signed(1 << 8)).astype(jnp.int32)
            + (s >= _signed(1 << 16)).astype(jnp.int32)
            + (s >= _signed(1 << 24)).astype(jnp.int32))


def _sig64(sh, sl):
    """significant_bytes of the uint64 value with signed-view planes."""
    return jnp.where(sh != _IMIN, 4 + _sig32(sh), _sig32(sl))


def _ptr(ndv):
    """Dictionary pointer bytes for ndv entries (== compression._ptr_bytes)."""
    return jnp.where(ndv <= 256, 1, jnp.where(ndv <= 65536, 2, 3))


def _lane_pick(x, mask):
    """(TR, 1) value of x at the one lane where mask holds."""
    return jnp.max(jnp.where(mask, x, _IMIN), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# The kernel.  hi/lo are (TR, TC) int32 signed views, w and cnt (TR, 1)
# int32 (width, rows stored in the segment); out is (TR, 1) int32.  `acc`
# are (TR, 1) int32 VMEM accumulators that live across the column axis.
# ---------------------------------------------------------------------------

def _segment_kernel(hi_ref, lo_ref, w_ref, cnt_ref, out_ref, *acc,
                    method: str, tile_c: int):
    j = pl.program_id(1)
    hi, lo = hi_ref[...], lo_ref[...]
    w, cnt = w_ref[...], cnt_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1)

    if method == "NS":
        (total,) = acc
        half = jnp.minimum(2 * jnp.minimum(_sig64(hi, lo), w) + 1, 2 * w)
        half = jnp.where(col + j * tile_c < cnt, half, 0)

        @pl.when(j == 0)
        def _():
            total[...] = jnp.zeros_like(total)

        total[...] += jnp.sum(half, axis=1, keepdims=True)

    elif method == "PREFIX":
        mnh, mnl, mxh, mxl = acc

        @pl.when(j == 0)
        def _():
            mnh[...] = jnp.full_like(mnh, _IMAX)
            mnl[...] = jnp.full_like(mnl, _IMAX)
            mxh[...] = jnp.full_like(mxh, _IMIN)
            mxl[...] = jnp.full_like(mxl, _IMIN)

        # this tile's lexicographic (hi, lo) min and max, folded into the
        # running ones
        tnh = jnp.min(hi, axis=1, keepdims=True)
        txh = jnp.max(hi, axis=1, keepdims=True)
        tnl = jnp.min(jnp.where(hi == tnh, lo, _IMAX), axis=1, keepdims=True)
        txl = jnp.max(jnp.where(hi == txh, lo, _IMIN), axis=1, keepdims=True)
        rnh, rnl, rxh, rxl = mnh[...], mnl[...], mxh[...], mxl[...]
        take_n = (tnh < rnh) | ((tnh == rnh) & (tnl < rnl))
        take_x = (txh > rxh) | ((txh == rxh) & (txl > rxl))
        mnh[...] = jnp.where(take_n, tnh, rnh)
        mnl[...] = jnp.where(take_n, tnl, rnl)
        mxh[...] = jnp.where(take_x, txh, rxh)
        mxl[...] = jnp.where(take_x, txl, rxl)

    else:  # GDICT / LDICT / RLE: 1 + number of adjacent changes
        changes, carry_h, carry_l = acc
        first = col == 0
        last = col == tile_c - 1
        fh, fl = _lane_pick(hi, first), _lane_pick(lo, first)
        lh, ll = _lane_pick(hi, last), _lane_pick(lo, last)

        @pl.when(j == 0)
        def _():
            changes[...] = jnp.ones_like(changes)
            carry_h[...] = fh
            carry_l[...] = fl

        # a lane rotation pairs every lane with a neighbour, cyclically, in
        # either direction; dropping the wrap-around pair leaves exactly
        # the tile's inner adjacent pairs
        neq = ((hi != pltpu.roll(hi, 1, 1))
               | (lo != pltpu.roll(lo, 1, 1))).astype(jnp.int32)
        wrap = ((fh != lh) | (fl != ll)).astype(jnp.int32)
        edge = ((fh != carry_h[...]) | (fl != carry_l[...])).astype(jnp.int32)
        changes[...] += jnp.sum(neq, axis=1, keepdims=True) - wrap + edge
        carry_h[...] = lh
        carry_l[...] = ll

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        meta = jnp.int32(_PAGE_META)
        cap = cnt * w + meta
        if method == "NS":
            out_ref[...] = (acc[0][...] + 1) >> 1
        elif method == "GDICT":
            ndv = acc[0][...]
            out_ref[...] = ndv * w + cnt * _ptr(ndv)
        elif method == "LDICT":
            ndv = acc[0][...]
            out_ref[...] = jnp.minimum(ndv * w + cnt * _ptr(ndv) + meta, cap)
        elif method == "RLE":
            out_ref[...] = jnp.minimum(acc[0][...] * (w + 2) + meta, cap)
        else:  # PREFIX
            xh = acc[0][...] ^ acc[2][...]
            xl = acc[1][...] ^ acc[3][...]
            diff = jnp.where((xh | xl) == 0, 0,
                             _sig64(xh ^ _IMIN, xl ^ _IMIN))
            common = jnp.maximum(w - diff, 0)
            out_ref[...] = jnp.minimum(
                common + cnt * (1 + w - common) + meta, cap)


_N_ACC = {"NS": 1, "GDICT": 3, "LDICT": 3, "RLE": 3, "PREFIX": 4}


def segment_tiles(rows: int, seg: int):
    """(tile_r, tile_c, rows_pad, cols_pad) for `rows` segments of `seg`
    values: column tiles of <= _MAX_TILE_C lanes, row tiles of
    <= _BLOCK_ELEMS values per plane block."""
    nct = -(-seg // _MAX_TILE_C)
    tile_c = _round_up(-(-seg // nct), _LANES)
    tile_r = max(_SUBLANES, min(_BLOCK_ELEMS // tile_c // _SUBLANES
                                * _SUBLANES, _round_up(rows, _SUBLANES)))
    return tile_r, tile_c, _round_up(rows, tile_r), nct * tile_c


def segment_call(hi, lo, w, cnt, *, method: str, tile_r: int, tile_c: int,
                 interpret: bool):
    """Per-segment payload bytes of (rows_pad, cols_pad) signed-view planes
    laid out as the module docstring says; returns (rows_pad, 1) int32."""
    r_pad, c_pad = hi.shape
    row = pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0))
    plane = pl.BlockSpec((tile_r, tile_c), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_segment_kernel, method=method, tile_c=tile_c),
        grid=(r_pad // tile_r, c_pad // tile_c),
        in_specs=[plane, plane, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((r_pad, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile_r, 1), jnp.int32)
                        for _ in range(_N_ACC[method])],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"codec_{method.lower()}",
    )(hi, lo, w, cnt)


def _edge_pad(x, width: int):
    if x.shape[1] == width:
        return x
    return jnp.pad(x, ((0, 0), (0, width - x.shape[1])), mode="edge")


# Launch shapes.  A codec program is keyed on its static shapes, so the
# shapes a call can take are kept to a small fixed set: the target axis
# goes in launches of CHUNK rows, and a page-local method lays its pages
# out `page_bucket(page)` lanes wide, the page length itself a traced
# value.  Every launch row is a real target: the last launch of a call
# ends at its last target, overlapping the launch before it, and a call of
# fewer than CHUNK targets repeats its last one.
CHUNK = 8
_WARM_THREADS = 8


def launch_starts(m: int) -> List[int]:
    """First target of each launch that covers m targets."""
    starts = list(range(0, max(m - CHUNK, 0) + 1, CHUNK))
    if m > CHUNK and starts[-1] + CHUNK < m:
        starts.append(m - CHUNK)
    return starts


def page_bucket(page: int) -> int:
    """Lanes per page row for pages of `page` rows: the power of two at or
    above it.  A bucket b holds page lengths b/2 < page <= b."""
    return 1 << (int(page) - 1).bit_length()


def _page_rows(n: int, bucket: int) -> int:
    """Page rows per target in bucket `bucket`: enough for the bucket's
    shortest page length."""
    return -(-n // (bucket // 2 + 1))


@functools.partial(jax.jit, static_argnames=("method", "bucket", "interpret"))
def _codec_call(x, w, page, *, method: str, bucket: int, interpret: bool):
    """(m,) int32 payload bytes of an (m, n) stack given as its (m, 2n)
    int32 words (lo on even lanes, hi on odd).  For page-local methods
    `page` (a traced int32, <= n) is the page length and `bucket` its
    `page_bucket`; ORD-IND methods ignore both.

    Page p of a target is laid out as one row of `bucket` lanes: lane l
    holds value min(p * page + min(l, page - 1), n - 1), so the lanes past
    the page's rows repeat its last value, as the NumPy reference's edge
    padding does (no new value, run or min/max), and the page rows past
    the last page repeat the target's last value and are left out of the
    sum.  The segment rows are padded to whole row tiles (weight 1, count
    1) and the pad rows dropped."""
    m, n = x.shape[0], x.shape[1] // 2
    if method in ORD_IND_METHODS:
        seg, nseg = n, 1
        hi = x[:, 1::2] ^ _IMIN
        lo = x[:, 0::2] ^ _IMIN
        cnt = jnp.full((1,), n, dtype=jnp.int32)
    else:
        seg, nseg = bucket, _page_rows(n, bucket)
        start = jnp.arange(nseg, dtype=jnp.int32) * page
        lane = jnp.minimum(jnp.arange(seg, dtype=jnp.int32), page - 1)
        idx = jnp.minimum(start[:, None] + lane[None, :], n - 1).reshape(-1)
        hi = (jnp.take(x, 2 * idx + 1, axis=1) ^ _IMIN).reshape(m * nseg,
                                                                 seg)
        lo = (jnp.take(x, 2 * idx, axis=1) ^ _IMIN).reshape(m * nseg, seg)
        cnt = jnp.clip(n - start, 1, page)
    if method in ("GDICT", "LDICT"):
        hi, lo = jax.lax.sort((hi, lo), dimension=1, num_keys=2)
    rows = m * nseg
    tile_r, tile_c, r_pad, c_pad = segment_tiles(rows, seg)
    pad_rows = ((0, r_pad - rows), (0, 0))
    hi = jnp.pad(_edge_pad(hi, c_pad), pad_rows)
    lo = jnp.pad(_edge_pad(lo, c_pad), pad_rows)
    w_seg = jnp.pad(jnp.repeat(w, nseg), (0, r_pad - rows),
                    constant_values=1)[:, None]
    cnt_seg = jnp.pad(jnp.tile(cnt, m), (0, r_pad - rows),
                      constant_values=1)[:, None]
    out = segment_call(hi, lo, w_seg, cnt_seg, method=method,
                       tile_r=tile_r, tile_c=tile_c, interpret=interpret)
    per_page = out[:rows, 0].reshape(m, nseg)
    if method not in ORD_IND_METHODS:
        per_page = jnp.where(start[None, :] < n, per_page, 0)
    return per_page.sum(axis=1)


def in_envelope(cols: np.ndarray, widths: np.ndarray) -> bool:
    """True when the int32 exactness proof covers this stack."""
    m, n = cols.shape
    return (n <= _MAX_ROWS and int(widths.max(initial=0)) <= _MAX_WIDTH
            and (m == 0 or n == 0 or int(cols.min()) >= 0))


@tracing.traced("kernel.codec")
def batched_codec_bytes(method: str, cols: np.ndarray, widths: np.ndarray,
                        rpp: int) -> np.ndarray:
    """Pallas twin of compression.BATCH_KERNELS[method] — bit-identical.

    cols is an (ntargets, nrows) int64 stack, widths (ntargets,), rpp the
    shared rows-per-page.  Inputs outside the int32 exactness envelope are
    routed to the NumPy reference (and counted) so the result is exact
    unconditionally.
    """
    cols = np.asarray(cols, dtype=np.int64)
    widths = np.asarray(widths, dtype=np.int64)
    m, n = cols.shape
    if m == 0 or n == 0:
        return np.zeros(m, dtype=np.int64)
    if not in_envelope(cols, widths):
        _counters["envelope_reroutes"] += 1
        from ..core import compression as _comp
        return _comp.BATCH_KERNELS[method](cols, widths, rpp)

    # the stack's own bytes, split into planes on the device; never the
    # int64 array itself, which jax would truncate to int32 without x64
    x = np.ascontiguousarray(cols).view(np.int32)
    w = widths.astype(np.int32)
    if method in ORD_DEP_METHODS:
        page = min(int(rpp), n)
        bucket = page_bucket(page)
    else:
        page, bucket = 0, 0
    page = np.int32(page)
    if m < CHUNK:                # repeat the last target up to a launch
        rep = np.minimum(np.arange(CHUNK), m - 1)
        x, w = x[rep], w[rep]
    starts = launch_starts(m)
    outs = []
    for i in starts:             # every launch queued before a read-back
        xs, ws = x[i:i + CHUNK], w[i:i + CHUNK]
        outs.append(_launch(xs, ws, page, method, bucket))
        _counters["kernel_calls"] += 1
        _counters["h2d_bytes"] += xs.nbytes + ws.nbytes + page.nbytes
    out = np.empty(max(m, CHUNK), dtype=np.int64)
    for i, o in zip(starts, outs):
        out[i:i + CHUNK] = np.asarray(o)
    _counters["d2h_bytes"] += 4 * CHUNK * len(outs)
    return out[:m]


def _launch(x: np.ndarray, w: np.ndarray, page: np.int32, method: str,
            bucket: int):
    return _codec_call(jnp.asarray(x, dtype=jnp.int32), jnp.asarray(w), page,
                       method=method, bucket=bucket,
                       interpret=pallas_interpret())


def programs(n: int, methods, pages) -> List[Tuple[str, int, int]]:
    """(method, n, page) of one launch of each program that
    `batched_codec_bytes` can run on stacks of n sample rows: one for each
    order-independent method and, for the page-local methods, one for each
    page bucket of the page lengths `pages` (each capped at n)."""
    buckets = sorted({page_bucket(min(int(p), n)) for p in pages})
    return [(method, n, page) for method in methods
            for page in ([min(b, n) for b in buckets]
                         if method in ORD_DEP_METHODS else [0])]


def warm_up(launches: Sequence[Tuple[str, int, int]]) -> int:
    """Run each launch `programs` lists once, on zeros, from _WARM_THREADS
    threads so that their compiles and cache loads overlap.  Returns how
    many ran."""
    def run(launch):
        method, n, page = launch
        bucket = page_bucket(page) if method in ORD_DEP_METHODS else 0
        _launch(np.zeros((CHUNK, 2 * n), np.int32), np.ones(CHUNK, np.int32),
                np.int32(page), method, bucket).block_until_ready()

    with ThreadPoolExecutor(max_workers=_WARM_THREADS) as pool:
        for _ in pool.map(run, launches):
            pass
    return len(launches)
