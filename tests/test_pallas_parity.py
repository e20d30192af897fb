"""CPU-interpret parity suite for the Pallas advisor kernels (tier 1).

Pins the two contracts the unified "jax" backend rests on:

* `kernels.codec_bytes.batched_codec_bytes` is BIT-IDENTICAL to the
  frozen NumPy codec references (`compression.BATCH_KERNELS`) for every
  input — inside the int32 exactness envelope via the uint32-plane
  kernels, outside it via the kernels' own NumPy routing — so the
  estimation stage under backend="jax" registers exactly the sizes the
  numpy backend registers.

* `kernels.planner_score` computes float32 values whose *internal*
  consistency is exact: the fused kernel's probability equals
  `prob_within` recomputed from its own (cm, cs) outputs bitwise (the
  replay / session-vs-fresh contract), and EXACT (mean=1, std=0) K-pads
  are the exact multiplicative identity (K-pad invariance, bitwise).
  Against the float64 NumPy reference the kernels are only
  float32-close — documented, since erf and arithmetic differ — which
  is why the numpy backend remains the advisor's parity reference.

Runs in Pallas interpret mode on CPU (no accelerator required); the CI
jax job executes exactly this file plus the backend-unification tests.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax", reason="parity suite needs jax")

from repro.core import compression as comp
from repro.core import errors as err
from repro.kernels import codec_bytes as ck
from repro.kernels import planner_score as ps

try:  # soft import: property twins only run where hypothesis exists
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except Exception:  # pragma: no cover - hypothesis is in requirements-dev
    HAVE_HYP = False

METHODS = ("NS", "GDICT", "LDICT", "PREFIX", "RLE")
RNG = np.random.default_rng(7)


def ref_bytes(method, cols, widths, rpp):
    return comp.BATCH_KERNELS[method](np.asarray(cols, dtype=np.int64),
                                      np.asarray(widths, dtype=np.int64),
                                      rpp)


def assert_codec_exact(method, cols, widths, rpp):
    cols = np.asarray(cols, dtype=np.int64)
    widths = np.asarray(widths, dtype=np.int64)
    got = ck.batched_codec_bytes(method, cols, widths, rpp)
    want = ref_bytes(method, cols, widths, rpp)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int64


# ---------------------------------------------------------------------------
# codec-bytes kernels: bit equality against the frozen NumPy references
# ---------------------------------------------------------------------------

class TestCodecBitEquality:
    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("shape,rpp", [
        ((1, 1), 1),        # single value, single-row pages
        ((3, 7), 3),        # partial last page
        ((5, 64), 16),      # exact pages
        ((8, 129), 128),    # one row past a lane boundary
        ((17, 200), 1000),  # rpp > nrows: one page
        ((4, 333), 1),      # rpp=1: every row its own page
        ((7, 1), 1),        # target counts off and on whole sublanes,
        ((8, 1), 4),        # one row each: the device takes the exact
        ((9, 1), 1),        # target count, with no pad of its own
        ((17, 1), 2),
        ((7, 50), 8),
        ((9, 31), 4),
        ((15, 37), 10),     # an overlapping last launch on both sides
        ((16, 37), 10),     # of a launch edge
        ((73, 37), 10),
    ])
    def test_random_small_values(self, method, shape, rpp):
        cols = RNG.integers(0, 1 << 16, size=shape)
        widths = RNG.integers(1, 9, size=shape[0])
        assert_codec_exact(method, cols, widths, rpp)

    @pytest.mark.parametrize("method", METHODS)
    def test_values_beyond_32_and_56_bits(self, method):
        # magnitudes crossing both uint32 planes: the kernels must stay
        # exact where float64 NS bit-lengths would already be unsafe
        cols = np.stack([
            RNG.integers(0, 1 << 62, size=96),
            np.full(96, (1 << 56) + 12345, dtype=np.int64),
            np.full(96, (1 << 32) - 1, dtype=np.int64),
            np.arange(96, dtype=np.int64) + (1 << 40),
        ])
        widths = np.array([8, 8, 8, 8])
        assert_codec_exact(method, cols, widths, 32)

    @pytest.mark.parametrize("method", METHODS)
    def test_degenerate_columns(self, method):
        cols = np.stack([
            np.zeros(50, dtype=np.int64),                 # all zero
            np.full(50, 9, dtype=np.int64),               # all equal
            np.repeat(np.arange(10), 5),                  # long runs
            np.arange(50, dtype=np.int64),                # all distinct
            np.sort(RNG.integers(0, 64, size=50)),        # sorted, dup-heavy
        ])
        widths = np.array([1, 2, 4, 8, 3])
        assert_codec_exact(method, cols, widths, 7)

    @pytest.mark.parametrize("method", METHODS)
    def test_out_of_envelope_routes_to_numpy(self, method):
        # width > 8 and negative values both leave the proven int32
        # envelope; the kernel must route to NumPy and stay exact
        wide = RNG.integers(0, 1 << 20, size=(3, 40))
        assert not ck.in_envelope(wide, np.array([16, 9, 32]))
        assert_codec_exact(method, wide, np.array([16, 9, 32]), 8)
        neg = RNG.integers(-1000, 1000, size=(2, 30))
        neg[0, 0] = -5
        assert not ck.in_envelope(neg, np.array([4, 4]))
        before = ck.counters()["envelope_reroutes"]
        assert_codec_exact(method, neg, np.array([4, 4]), 8)
        assert ck.counters()["envelope_reroutes"] == before + 1

    @pytest.mark.parametrize("method", METHODS)
    def test_empty_stack(self, method):
        got = ck.batched_codec_bytes(
            method, np.zeros((0, 5), dtype=np.int64),
            np.zeros(0, dtype=np.int64), 4)
        assert got.shape == (0,)

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("rpp", [31, 32, 33, 64, 65, 127, 128, 129,
                                     273, 682, 1638])
    def test_unaligned_rows_per_page(self, method, rpp):
        # rows-per-page values of real index widths that are not lane
        # multiples, and page lengths on both sides of page-bucket edges
        # (the pages past the last and the lanes past a page's rows change
        # nothing), with a partial last page
        cols = RNG.integers(0, 1 << 12, size=(3, 3 * rpp + 101))
        cols[1] = np.sort(cols[1])          # page-local runs and dictionaries
        assert_codec_exact(method, cols, np.array([2, 4, 8]), rpp)

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("n,rpp", [
        (5000, 4),      # NS/GDICT: column tiles; paged: row tiles
        (2500, 1),      # paged: 7500 one-row pages over several row tiles
        (7000, 3000),   # paged: pages wider than one column tile
    ])
    def test_multi_tile(self, method, n, rpp):
        m = 3
        seg = n if method in ck.ORD_IND_METHODS else min(rpp, n)
        rows = m * (-(-n // seg))
        tile_r, tile_c, r_pad, c_pad = ck.segment_tiles(rows, seg)
        assert r_pad // tile_r > 1 or c_pad // tile_c > 1
        cols = np.stack([
            RNG.integers(0, 1 << 20, size=n),
            np.repeat(np.arange(-(-n // 37)), 37)[:n],    # runs across tiles
            np.sort(RNG.integers(0, 50, size=n)),
        ])
        assert_codec_exact(method, cols, np.array([3, 4, 8]), rpp)

    @pytest.mark.parametrize("method", METHODS)
    def test_change_at_tile_boundary(self, method):
        """Column tile boundaries that fall inside a run of equal values
        (GDICT's sorted rows, RLE's runs) or exactly on a change: each
        counted once."""
        n = 5000
        _, tile_c, _, c_pad = ck.segment_tiles(8, n)
        assert c_pad // tile_c > 1
        run = np.arange(n, dtype=np.int64)
        run[tile_c - 40:tile_c + 40] = tile_c        # run across the boundary
        step = np.arange(n, dtype=np.int64) // tile_c  # change on it
        edge = np.zeros(n, dtype=np.int64)
        edge[tile_c:] = 1                              # one change, on it
        cols = np.stack([run, step, edge, run[::-1].copy()])
        assert_codec_exact(method, cols, np.array([4, 2, 1, 8]), n)
        assert_codec_exact(method, cols, np.array([4, 2, 1, 8]), 2 * tile_c)

    @pytest.mark.parametrize("method", METHODS)
    def test_plane_extremes(self, method):
        """Signed-view min/max and significant bytes at the extremes of
        both uint32 planes (lo's sign bit, all-ones lo, the largest hi),
        and the device's split of the int64 words into those planes:
        every pairing of the hi words 0, 1, 2^31-1 with the lo words 0,
        2^31-1, 2^31, 2^32-1 is among the values."""
        top = (1 << 63) - 1
        vals = np.array([
            0, 1, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF, 1 << 32,
            (1 << 32) + 0xFFFFFFFF, (0x7FFFFFFF << 32), top,
            (0x7FFFFFFF << 32) | 0x80000000, 0x80000001, 0x7FFFFFFE,
            (1 << 32) | 0x7FFFFFFF, (1 << 32) | 0x80000000,
            (0x7FFFFFFF << 32) | 0x7FFFFFFF,
        ], dtype=np.int64)
        assert {(h << 32) | lo for h in (0, 1, 0x7FFFFFFF)
                for lo in (0, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF)
                } <= set(vals.tolist())
        pairs = np.stack([np.roll(vals, k) for k in range(len(vals))])
        cols = np.concatenate([pairs, pairs[:, ::-1]], axis=0)
        widths = np.full(cols.shape[0], 8)
        for rpp in (2, 3, 12):
            assert_codec_exact(method, cols, widths, rpp)
        # pages of two lo-plane values straddling the sign bit
        lo_mix = np.tile(np.array([0x7FFFFFFF, 0x80000000], dtype=np.int64),
                         (2, 8))
        lo_mix[1] += 5 << 32
        assert_codec_exact(method, lo_mix, np.array([4, 8]), 2)

    @pytest.mark.parametrize("method", ["GDICT", "PREFIX"])
    def test_hi_words_reach_the_device(self, method):
        """Two stacks equal in their lo words and different in their hi
        words have different sizes, so a hand-off that kept only the low
        32 bits of each value (jax truncates int64 to int32 without x64)
        could not match the reference."""
        n = 96
        lo = np.arange(n, dtype=np.int64) % 4
        same_hi = np.stack([lo, lo + (3 << 32)])
        mixed_hi = np.stack([lo + ((np.arange(n) % 7) << 32),
                             lo + ((np.arange(n) // 4 % 3) << 40)])
        widths = np.array([8, 8])
        want_same = ref_bytes(method, same_hi, widths, 32)
        want_mixed = ref_bytes(method, mixed_hi, widths, 32)
        assert (want_same != want_mixed).all()
        assert_codec_exact(method, same_hi, widths, 32)
        assert_codec_exact(method, mixed_hi, widths, 32)

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("layout", ["row_slice", "transposed"])
    def test_non_contiguous_stack(self, method, layout):
        """Stacks that are views, not contiguous arrays: every other row of
        a larger stack, and the transpose of a (rows, targets) array."""
        base = RNG.integers(0, 1 << 40, size=(18, 70))
        base[::3] >>= 20                  # some rows with a zero hi word
        if layout == "row_slice":
            cols = base[1::2]
        else:
            cols = np.ascontiguousarray(base[:9].T).T
        assert not cols.flags["C_CONTIGUOUS"]
        widths = RNG.integers(1, 9, size=cols.shape[0])
        assert_codec_exact(method, cols, widths, 16)

    @pytest.mark.parametrize("m", [1, 7, 8, 9, 17, 73])
    def test_transfer_counters(self, m):
        """The launches (one per `launch_starts` entry) send CHUNK rows of
        the stack's own bytes (n * 8 each), their widths (4 each) and one
        int32 page length, and read back one int32 a row: no pad row, and
        no row past the ones the call covers."""
        n = 45
        cols = RNG.integers(0, 1 << 33, size=(m, n))
        widths = RNG.integers(1, 9, size=m)
        launches = len(ck.launch_starts(m))
        assert launches == -(-m // ck.CHUNK)
        for method in METHODS:
            before = ck.counters()
            assert_codec_exact(method, cols, widths, 10)
            after = ck.counters()
            assert after["kernel_calls"] == before["kernel_calls"] + launches
            assert (after["h2d_bytes"] - before["h2d_bytes"]
                    == launches * (ck.CHUNK * (n * 8 + 4) + 4))
            assert (after["d2h_bytes"] - before["d2h_bytes"]
                    == launches * ck.CHUNK * 4)

    @pytest.mark.parametrize("method", METHODS)
    def test_min_max_fold_across_tiles(self, method):
        """A page wider than one column tile whose min (or max) sits in a
        later tile with the same hi plane: the running lexicographic
        min/max must take it on lo alone."""
        n = 3000
        _, tile_c, _, c_pad = ck.segment_tiles(8, n)
        assert c_pad // tile_c > 1
        low = np.full(n, 0x10000, dtype=np.int64)
        low[tile_c:] = 0x1FFFF
        low[-1] = 0xFFFF                 # min in the later tile
        high = np.full(n, 0x1FFFF, dtype=np.int64)
        high[tile_c:] = 0x10000
        high[-1] = 0x2FFFF               # max in the later tile
        cols = np.stack([low, high, low + (5 << 32), high + (5 << 32)])
        assert_codec_exact(method, cols, np.array([4, 4, 8, 8]), n)

    def test_dispatcher_routes_jax_backend(self):
        cols = RNG.integers(0, 1 << 10, size=(6, 90))
        widths = RNG.integers(1, 9, size=6)
        for method in METHODS:
            np.testing.assert_array_equal(
                comp.batched_bytes(method, cols, widths, 11, backend="jax"),
                comp.batched_bytes(method, cols, widths, 11))

    if HAVE_HYP:
        @settings(max_examples=30, deadline=None)
        @given(st.integers(1, 6), st.integers(1, 80), st.integers(1, 96),
               st.integers(0, 2 ** 63 - 1), st.integers(1, 8))
        def test_property_twin(self, m, n, rpp, top, w):
            cols = np.remainder(
                np.arange(m * n, dtype=np.uint64) * np.uint64(2654435761),
                np.uint64(top) + np.uint64(1)).astype(np.int64).reshape(m, n)
            widths = np.full(m, w, dtype=np.int64)
            for method in METHODS:
                assert_codec_exact(method, cols, widths, rpp)


class TestCodecShapes:
    """The programs a codec call lowers are a small fixed set: the target
    axis goes in launches of `CHUNK` rows, and a page-local method lays
    its pages out `page_bucket(page)` lanes wide, the page length a
    traced value; every shape stays bit-equal to the NumPy references."""

    def test_launches_and_buckets_are_a_small_fixed_set(self):
        for m in range(1, 301):
            starts = ck.launch_starts(m)
            assert len(starts) == -(-m // ck.CHUNK)
            assert starts == sorted(set(starts)) and starts[0] == 0
            covered = set()
            for i in starts:            # every launch holds real rows only
                assert 0 <= i and (i + ck.CHUNK <= m or m < ck.CHUNK)
                covered.update(range(i, min(i + ck.CHUNK, m)))
            assert covered == set(range(m))
        buckets = {ck.page_bucket(p) for p in range(1, 2049)}
        assert buckets == {2 ** k for k in range(12)}
        for p in range(1, 2049):
            assert ck.page_bucket(p) // 2 < p <= ck.page_bucket(p)

    def test_one_program_per_bucket(self):
        """Calls of any target count, across launch and bucket edges,
        lower one program for each (method, page bucket) they use, and no
        other."""
        n = 59                          # a sample length no other test uses
        cols = RNG.integers(0, 1 << 20, size=(73, n))
        widths = RNG.integers(1, 9, size=73)
        before = ck._codec_call._cache_size()
        used = set()
        for m in (1, 7, 8, 9, 16, 17, 72, 73):
            for method, rpp in (("NS", 0), ("LDICT", 9), ("LDICT", 12),
                                ("LDICT", 16), ("LDICT", 17)):
                assert_codec_exact(method, cols[:m], widths[:m], rpp)
                used.add((method, ck.page_bucket(rpp)
                          if method == "LDICT" else 0))
        assert len(used) == 3
        assert ck._codec_call._cache_size() - before == len(used)

    def test_warm_up_runs_every_program_once(self):
        """After `warm_up`, calls of any target count and any page length
        in the warmed range lower nothing new."""
        n = 83
        launches = ck.warm_up(ck.programs(n, ("NS", "LDICT"),
                                          range(20, 70)))
        # NS: one; LDICT: page buckets 32, 64, 128 (pages 20..69)
        assert launches == 1 + 3
        size = ck._codec_call._cache_size()
        cols = RNG.integers(0, 1 << 20, size=(75, n))
        widths = RNG.integers(1, 9, size=75)
        for m, rpp in ((75, 20), (9, 33), (1, 64), (66, 65), (17, 69)):
            for method in ("NS", "LDICT"):
                assert_codec_exact(method, cols[:m], widths[:m], rpp)
        assert ck._codec_call._cache_size() == size


# ---------------------------------------------------------------------------
# planner kernels: float32 closeness to the f64 reference, exact internal
# consistency (the replay contract), exact K-pad invariance
# ---------------------------------------------------------------------------

def random_rvs(nc, k, nf, seed):
    rng = np.random.default_rng(seed)
    m = rng.uniform(0.6, 1.4, size=(nc, k, nf))
    s = rng.uniform(0.0, 0.3, size=(nc, k, nf))
    s[rng.random(s.shape) < 0.2] = 0.0  # exercise the indicator branch
    dm = rng.uniform(0.8, 1.2, size=(nc, 1))
    msq = dm * dm
    vt = msq + rng.uniform(0.0, 0.1, size=(nc, 1))
    return m, s, dm, vt, msq


def staged_reference(m, s, dm, vt, mq, mask, e):
    """Float64 NumPy re-expression of compose + prob (the goodman fold)."""
    e_prod = m[:, 0, :].copy()
    v_term = s[:, 0, :] ** 2 + e_prod ** 2
    e2 = e_prod ** 2
    for kk in range(1, m.shape[1]):
        mk, sk = m[:, kk, :], s[:, kk, :]
        e_prod = e_prod * mk
        v_term = v_term * (sk * sk + mk * mk)
        e2 = e2 * (mk * mk)
    cm = e_prod * dm
    cs = np.sqrt(np.maximum(v_term * vt - e2 * mq, 0.0))
    p = np.zeros_like(cm)
    ii = mask.nonzero()
    p[ii] = err.prob_within_batch(cm[ii], cs[ii], e)
    return cm, cs, p


class TestErfF32:
    def test_against_math_erf(self):
        import math
        x = np.linspace(-6.0, 6.0, 4001)
        got = np.asarray(ps.erf_f32(np.float32(x)), dtype=np.float64)
        want = np.array([math.erf(v) for v in np.float32(x).tolist()])
        np.testing.assert_allclose(got, want, atol=2e-5)
        # odd, and saturated beyond the clamp
        np.testing.assert_array_equal(got, -got[::-1])
        assert got[0] == -1.0 and got[-1] == 1.0


class TestProbWithin:
    def test_indicator_branch_exact(self):
        e = 0.1
        lo, hi = 1.0 / (1.0 + e), 1.0 + e
        means = np.array([0.2, lo, 1.0, hi, 1.6, np.float64(np.float32(lo))])
        stds = np.zeros_like(means)
        got = ps.prob_within(means, stds, e)
        # std=0: pure indicator; f32 rounding of the bounds could only
        # matter at the exact boundary, where both sides round the same
        assert set(np.unique(got)) <= {0.0, 1.0}
        np.testing.assert_array_equal(
            got[[0, 2, 4]], err.prob_within_batch(means, stds, e)[[0, 2, 4]])

    @pytest.mark.parametrize("n,e", [(1, 0.05), (7, 0.1), (128, 0.2),
                                     (129, 0.1), (1000, 0.15)])
    def test_erf_branch_close(self, n, e):
        rng = np.random.default_rng(n)
        means = rng.uniform(0.5, 1.5, size=n)
        stds = rng.uniform(1e-6, 0.5, size=n)
        got = ps.prob_within(means, stds, e)
        want = err.prob_within_batch(means, stds, e)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_shapes_and_empty(self):
        assert ps.prob_within(np.zeros(0), np.zeros(0), 0.1).shape == (0,)
        m2 = np.full((3, 4), 1.0)
        s2 = np.zeros((3, 4))
        assert ps.prob_within(m2, s2, 0.1).shape == (3, 4)

    if HAVE_HYP:
        @settings(max_examples=40, deadline=None)
        @given(st.floats(0.3, 2.5), st.floats(0.0, 1.0), st.floats(0.02, 0.5))
        def test_property_twin(self, mean, std, e):
            got = float(ps.prob_within(np.array([mean]), np.array([std]),
                                       e)[0])
            want = float(err.prob_within_batch(np.array([mean]),
                                               np.array([std]), e)[0])
            assert abs(got - want) <= 3e-5
            assert 0.0 <= got <= 1.0


class TestFusedScore:
    E, Q = 0.1, 0.9

    def test_staged_f64_reference_close(self):
        nc, k, nf = 11, 3, 5
        m, s, dm, vt, mq = random_rvs(nc, k, nf, seed=1)
        mask67 = np.ones((nc, nf), dtype=bool)
        mask67[2] = False
        cm, cs, p, _, _ = ps.fused_score(m, s, dm, vt, mq, mask67, None,
                                         None, self.E, self.Q)
        cm_r, cs_r, p_r = staged_reference(m, s, dm, vt, mq, mask67, self.E)
        np.testing.assert_allclose(cm, cm_r, rtol=1e-5)
        np.testing.assert_allclose(cs, cs_r, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(p, p_r, atol=5e-5)
        # masked-out rows are exactly zero on both sides
        assert (p[2] == 0.0).all()

    def test_prob_consistency_bitwise(self):
        """THE replay contract: recomputing the probability from the fused
        kernel's own (cm, cs) through prob_within reproduces its p
        bitwise (same _prob_expr, float32-exact in-and-out)."""
        nc, k, nf = 9, 2, 4
        m, s, dm, vt, mq = random_rvs(nc, k, nf, seed=2)
        mask67 = np.ones((nc, nf), dtype=bool)
        cm, cs, p, _, _ = ps.fused_score(m, s, dm, vt, mq, mask67, None,
                                         None, self.E, self.Q)
        again = ps.prob_within(cm, cs, self.E)
        np.testing.assert_array_equal(p, again)

    def test_kpad_invariance_bitwise(self):
        """EXACT (mean=1, std=0) K-pads are the exact float32
        multiplicative identity: folding K=2 padded to K=5 is bitwise
        the K=2 fold."""
        nc, nf = 6, 3
        m, s, dm, vt, mq = random_rvs(nc, 2, nf, seed=3)
        mask67 = np.ones((nc, nf), dtype=bool)
        pad_m = np.concatenate([m, np.ones((nc, 3, nf))], axis=1)
        pad_s = np.concatenate([s, np.zeros((nc, 3, nf))], axis=1)
        a = ps.fused_score(m, s, dm, vt, mq, mask67, None, None,
                           self.E, self.Q)
        b = ps.fused_score(pad_m, pad_s, dm, vt, mq, mask67, None, None,
                           self.E, self.Q)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_power_of_two_axes_one_program(self):
        """Candidate and child counts go up to powers of two: records of
        9..16 candidates and 3..4 children share one program, and the
        pads change no output bit (K=3 against K=4 with an EXACT child)."""
        before = ps._fused_call._cache_size()
        for nc in (9, 12, 16):
            m, s, dm, vt, mq = random_rvs(nc, 3, 5, seed=nc)
            mask67 = np.ones((nc, 5), dtype=bool)
            a = ps.fused_score(m, s, dm, vt, mq, mask67, None, None,
                               self.E, 0.37)
            b = ps.fused_score(np.concatenate([m, np.ones((nc, 1, 5))], 1),
                               np.concatenate([s, np.zeros((nc, 1, 5))], 1),
                               dm, vt, mq, mask67, None, None, self.E, 0.37)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
        assert ps._fused_call._cache_size() - before == 1

    @pytest.mark.parametrize("shapes", [
        ((3, 1), (5, 4), (11, 2)),       # 19 rows, children 1..4
        ((1, 7), (9, 3)),                # K=3 padded to the K=7 record's 8
        ((8, 2), (8, 2)),                # two whole 8-row buckets
        ((2, 16), (1, 1), (4, 5), (6, 9), (1, 3)),
    ])
    def test_batch_bitwise_per_record(self, shapes):
        """One launch over several records of different candidate and
        child counts returns each record's cm/cs/p bitwise as its own
        `fused_score` launch does: candidate rows are independent, and
        EXACT child pads up to the widest record are the fold's identity."""
        nf = 5
        records = []
        for i, (nc, k) in enumerate(shapes):
            m, s, dm, vt, mq = random_rvs(nc, k, nf, seed=10 + i)
            rng = np.random.default_rng(20 + i)
            mask67 = rng.random((nc, nf)) < 0.6
            pre9 = extra = None
            if i % 2:
                pre9 = ~mask67 & (rng.random((nc, nf)) < 0.7)
                extra = rng.uniform(0.1, 2.0, size=(nc, nf))
            records.append((m, s, dm, vt, mq, mask67, pre9, extra))
        calls = ps.counters()["fused_calls"]
        got = ps.fused_score_batch(records, self.E, 0.37)
        assert ps.counters()["fused_calls"] == calls + 1
        assert len(got) == len(records)
        for rec, scores in zip(records, got):
            want = ps.fused_score(*rec, self.E, 0.37)
            assert (want[2] > 0).any()
            for x, y in zip(scores, want[:3]):
                assert x.shape == y.shape
                np.testing.assert_array_equal(x, y)

    def test_warm_up_runs_every_program_once(self, monkeypatch):
        monkeypatch.setattr(ps, "MAX_CANDIDATES", 32)
        monkeypatch.setattr(ps, "MAX_CHILDREN", 8)
        monkeypatch.setattr(ps, "MAX_CELLS", 128)
        # candidate buckets 8 and 16 with children 1..8, 32 with 1..4
        assert ps.warm_up(self.E, 0.41) == 2 * 4 + 3
        size = ps._fused_call._cache_size()
        for nc, k in ((3, 1), (8, 2), (11, 3), (16, 8), (13, 7), (32, 4),
                      (17, 3)):
            assert ps.fits(nc, k)
            m, s, dm, vt, mq = random_rvs(nc, k, 5, seed=k)
            ps.fused_score(m, s, dm, vt, mq, np.ones((nc, 5), dtype=bool),
                           None, None, self.E, 0.41)
        # batches the walk can gather: any records whose rows fit the cap
        for shapes in (((3, 1), (5, 4), (11, 2)), ((20, 3), (9, 4)),
                       ((1, 8), (2, 5), (4, 1))):
            assert ps.fits(sum(nc for nc, _ in shapes),
                           max(k for _, k in shapes))
            recs = []
            for nc, k in shapes:
                m, s, dm, vt, mq = random_rvs(nc, k, 5, seed=nc)
                recs.append((m, s, dm, vt, mq, np.ones((nc, 5), dtype=bool),
                             None, None))
            ps.fused_score_batch(recs, self.E, 0.41)
        assert ps._fused_call._cache_size() == size
        assert not ps.fits(33, 1) and not ps.fits(17, 8)
        assert not ps.fits(8, 9)

    def test_program_set_holds_the_batch_cap(self):
        """The warmed set reaches the walk's cap: 128 candidate rows of up
        to 32 children, or 64 rows of up to 64 (a 128 x 64 stack does not
        fit a v5e kernel's scoped VMEM), and every launch `fits` admits
        is one of its programs."""
        progs = set(ps.programs())
        assert ps.MAX_CANDIDATES == 128 and ps.MAX_CHILDREN == 64
        assert {(128, 32), (64, 64), (8, 64)} <= progs
        assert (128, 64) not in progs
        assert len(progs) == 7 * 4 + 6
        for rows in range(1, 140):
            for k in range(1, 70):
                if ps.fits(rows, k):
                    assert ps._buckets(rows, k) in progs
        assert ps.fits(128, 32) and ps.fits(64, 64)
        assert not ps.fits(129, 1) and not ps.fits(65, 33)

    def test_warm_once_per_accuracy_target(self, monkeypatch):
        """`warm_once` runs the set once per (e, q) in a compiling
        process, and not at all where kernels are interpreted."""
        ran = []
        monkeypatch.setattr(ps, "_warmed", set())
        monkeypatch.setattr(ps, "warm_up",
                            lambda e, q: ran.append((e, q)) or 34)
        assert ps.warm_once(0.5, 0.9) == 0
        monkeypatch.setattr(ps, "pallas_interpret", lambda: False)
        assert ps.warm_once(0.5, 0.9) == 34
        assert ps.warm_once(0.5, 0.9) == 0
        assert ps.warm_once(0.5, 1.1) == 34
        assert ran == [(0.5, 0.9), (0.5, 1.1)]

    def test_winner_indices_match_host_argmax(self):
        nc, k, nf = 14, 2, 6
        m, s, dm, vt, mq = random_rvs(nc, k, nf, seed=4)
        mask67 = np.zeros((nc, nf), dtype=bool)
        mask67[: nc // 2] = True
        pre9 = np.zeros((nc, nf), dtype=bool)
        pre9[nc // 2:] = True
        extra = np.abs(np.random.default_rng(5).normal(size=(nc, nf))) + 0.1
        cm, cs, p, w6, w9 = ps.fused_score(m, s, dm, vt, mq, mask67, pre9,
                                           extra, self.E, 0.2)
        sat = p >= 0.2
        for f in range(nf):
            elig = mask67[:, f] & sat[:, f]
            if elig.any():
                pe = np.where(elig, p[:, f], -1.0)
                assert w6[f] == int(np.flatnonzero(pe == pe.max())[0])
            else:
                assert w6[f] == 2 ** 31 - 1
                ok9 = pre9[:, f] & sat[:, f]
                if ok9.any():
                    xe = np.where(ok9, extra[:, f], np.inf)
                    assert w9[f] == int(np.flatnonzero(xe == xe.min())[0])
