"""Deep statistics sweeps — arg-reductions, moments, and order statistics
over axis × split × keepdims grids with uneven extents; weighted variants;
scipy-free higher-moment oracles (reference
heat/core/tests/test_statistics.py, 1,334 LoC)."""

import numpy as np
import pytest

import heat_tpu as ht
from .basic_test import TestCase


def _skew_np(a, axis=None, bias=True):
    m = a.mean(axis=axis, keepdims=True)
    d = a - m
    m2 = (d**2).mean(axis=axis)
    m3 = (d**3).mean(axis=axis)
    g = m3 / np.power(m2, 1.5)
    if bias:
        return g
    n = a.shape[axis] if axis is not None else a.size
    return np.sqrt(n * (n - 1)) / (n - 2) * g


def _kurt_np(a, axis=None, fisher=True):
    m = a.mean(axis=axis, keepdims=True)
    d = a - m
    m2 = (d**2).mean(axis=axis)
    m4 = (d**4).mean(axis=axis)
    k = m4 / m2**2
    return k - 3.0 if fisher else k


class TestArgReductionGrid(TestCase):
    def _t(self):
        rng = np.random.default_rng(61)
        return rng.standard_normal((self.comm.size + 1, 4, 3)).astype(np.float32)

    def test_argmax_argmin_every_axis_split(self):
        t = self._t()
        for split in (None, 0, 1, 2):
            x = ht.array(t, split=split)
            for axis in (0, 1, 2):
                np.testing.assert_array_equal(
                    ht.argmax(x, axis=axis).numpy(), t.argmax(axis=axis)
                )
                np.testing.assert_array_equal(
                    ht.argmin(x, axis=axis).numpy(), t.argmin(axis=axis)
                )

    def test_global_argmax_flat_index(self):
        t = self._t()
        for split in (None, 0, 1):
            x = ht.array(t, split=split)
            assert int(ht.argmax(x)) == int(t.argmax())
            assert int(ht.argmin(x)) == int(t.argmin())

    def test_argmax_ties_first_wins(self):
        a = np.asarray([1.0, 3.0, 3.0, 0.0], dtype=np.float32)
        for split in (None, 0):
            assert int(ht.argmax(ht.array(a, split=split))) == 1

    def test_max_min_keepdims(self):
        t = self._t()
        x = ht.array(t, split=0)
        got = ht.max(x, axis=1, keepdims=True)
        self.assert_array_equal(got, t.max(axis=1, keepdims=True))
        got = ht.min(x, axis=(0, 2), keepdims=True)
        self.assert_array_equal(got, t.min(axis=(0, 2), keepdims=True))


class TestMomentsGrid(TestCase):
    def _m(self):
        rng = np.random.default_rng(62)
        return rng.uniform(-3, 3, size=(2 * self.comm.size + 1, 5)).astype(np.float32)

    def test_mean_std_var_axis_grid(self):
        m = self._m()
        for split in (None, 0, 1):
            x = ht.array(m, split=split)
            for axis in (None, 0, 1):
                np.testing.assert_allclose(
                    np.asarray(ht.mean(x, axis=axis).numpy() if axis is not None else float(ht.mean(x))),
                    m.mean(axis=axis), rtol=1e-4, atol=1e-5,
                )
                np.testing.assert_allclose(
                    np.asarray(ht.var(x, axis=axis).numpy() if axis is not None else float(ht.var(x))),
                    m.var(axis=axis), rtol=1e-3, atol=1e-4,
                )

    def test_skew_bias_toggle(self):
        m = self._m()
        x = ht.array(m, split=0)
        np.testing.assert_allclose(
            np.asarray(ht.skew(x, axis=0, unbiased=False).numpy()),
            _skew_np(m.astype(np.float64), axis=0, bias=True),
            rtol=1e-3, atol=1e-3,
        )

    def test_kurtosis_fisher_toggle(self):
        m = self._m()
        x = ht.array(m, split=0)
        for fisher in (True, False):
            np.testing.assert_allclose(
                np.asarray(ht.kurtosis(x, axis=0, fisher=fisher).numpy()),
                _kurt_np(m.astype(np.float64), axis=0, fisher=fisher),
                rtol=1e-3, atol=1e-3,
            )

    def test_moments_constant_input(self):
        a = np.full(3 * self.comm.size, 2.5, dtype=np.float32)
        x = ht.array(a, split=0)
        assert abs(float(ht.mean(x)) - 2.5) < 1e-6
        assert abs(float(ht.var(x))) < 1e-6


class TestAverageWeighted(TestCase):
    def test_weighted_axis_and_returned(self):
        p = self.comm.size
        m = np.arange((p + 1) * 3, dtype=np.float32).reshape(p + 1, 3)
        w = np.arange(1, p + 2, dtype=np.float32)
        x = ht.array(m, split=0)
        wx = ht.array(w, split=0)
        got, wsum = ht.average(x, axis=0, weights=wx, returned=True)
        want = np.average(m, axis=0, weights=w)
        np.testing.assert_allclose(got.numpy(), want, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(wsum.numpy()), np.full(3, w.sum()), rtol=1e-6)

    def test_unweighted_matches_mean(self):
        m = np.arange(12, dtype=np.float32).reshape(4, 3)
        x = ht.array(m, split=1)
        np.testing.assert_allclose(
            ht.average(x, axis=1).numpy(), m.mean(axis=1), rtol=1e-6
        )


class TestOrderStatisticsGrid(TestCase):
    def _a(self):
        rng = np.random.default_rng(63)
        return rng.standard_normal(4 * self.comm.size + 3).astype(np.float32)

    def test_median_even_odd_lengths(self):
        for extra in (0, 1):
            a = self._a()[: len(self._a()) - extra]
            for split in (None, 0):
                got = float(ht.median(ht.array(a, split=split)))
                np.testing.assert_allclose(got, np.median(a), rtol=1e-5)

    @pytest.mark.slow
    def test_percentile_interpolations(self):
        a = self._a()
        x = ht.array(a, split=0)
        for q in (0, 25, 50, 75, 100):
            for method in ("linear", "lower", "higher", "nearest", "midpoint"):
                got = float(ht.percentile(x, q, interpolation=method))
                want = float(np.percentile(a, q, method=method))
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_percentile_nearest_full_matrix(self):
        # numpy rounds half positions to even; axis tuples, n-D q, keepdims,
        # and NaN propagation must all match (regression: the jnp 'nearest'
        # delegation rounded half positions down)
        rng = np.random.default_rng(68)
        t = rng.standard_normal((3, 4, 5)).astype(np.float32)
        x = ht.array(t, split=0)
        for axis in (None, 1, (0, 1), (1, 2)):
            for q in (50, [25, 50], [[10, 20], [30, 40]]):
                for kd in (False, True):
                    g = ht.percentile(x, q, axis=axis, interpolation="nearest", keepdims=kd)
                    g = np.asarray(g.numpy())
                    w = np.percentile(t, q, axis=axis, method="nearest", keepdims=kd)
                    np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=f"{axis} {q} {kd}")
        tn = t.copy()
        tn[1, 2, 3] = np.nan
        xn = ht.array(tn, split=0)
        for axis in (None, 1, (1, 2)):
            g = np.asarray(ht.percentile(xn, 50, axis=axis, interpolation="nearest").numpy())
            w = np.percentile(tn, 50, axis=axis, method="nearest")
            np.testing.assert_allclose(g, w, rtol=1e-6, equal_nan=True)

    def test_percentile_nearest_exact_half_positions(self):
        # q/100*(n-1) landing on exact .5 must round half-to-even on every
        # backend (regression: on-device rounding under the TPU backend's
        # emulated float64 mis-rounds exact halves — round(0.5) came out -1,
        # wrapping the take to the LAST element)
        for n, qs in ((6, [10, 30, 50, 70, 90]), (16, [10, 30, 50, 70, 90]), (11, [5, 15, 25, 35, 45, 55, 65, 75, 85, 95])):
            a = np.arange(float(n))
            x = ht.array(a, split=0)
            got = np.asarray(ht.percentile(x, qs, interpolation="nearest").numpy())
            want = np.percentile(a, qs, method="nearest")
            np.testing.assert_array_equal(got, want, err_msg=f"n={n}")

    def test_percentile_axis_keepdims(self):
        p = self.comm.size
        m = np.random.default_rng(64).standard_normal((p + 2, 6)).astype(np.float32)
        x = ht.array(m, split=0)
        got = ht.percentile(x, 30, axis=1, keepdims=True)
        want = np.percentile(m, 30, axis=1, keepdims=True)
        np.testing.assert_allclose(np.asarray(got.numpy()), want, rtol=1e-4, atol=1e-5)


class TestHistogramGrid(TestCase):
    def test_histogram_bins_and_range(self):
        rng = np.random.default_rng(65)
        a = rng.uniform(-4, 4, size=6 * self.comm.size).astype(np.float32)
        for split in (None, 0):
            x = ht.array(a, split=split)
            for bins, rng_ in [(10, None), (5, (-2.0, 2.0)), (16, (-4.0, 4.0))]:
                hist, edges = ht.histogram(x, bins=bins, range=rng_)
                whist, w_edges = np.histogram(a, bins=bins, range=rng_)
                np.testing.assert_array_equal(np.asarray(hist.numpy()), whist)
                np.testing.assert_allclose(np.asarray(edges.numpy()), w_edges, rtol=1e-5)

    def test_histc_torch_semantics(self):
        a = np.asarray([0.5, 1.5, 2.5, 2.5, 3.5], dtype=np.float32)
        got = ht.histc(ht.array(a, split=0), bins=4, min=0.0, max=4.0)
        np.testing.assert_array_equal(np.asarray(got.numpy()), [1, 1, 2, 1])

    def test_bincount_minlength_weights(self):
        v = np.asarray([0, 1, 1, 3], dtype=np.int64)
        w = np.asarray([0.5, 1.0, 1.0, 2.0], dtype=np.float32)
        for split in (None, 0):
            x = ht.array(v, split=split)
            got = ht.bincount(x, minlength=6)
            np.testing.assert_array_equal(
                np.asarray(got.numpy()), np.bincount(v, minlength=6)
            )
            gw = ht.bincount(x, weights=ht.array(w, split=split))
            np.testing.assert_allclose(
                np.asarray(gw.numpy()), np.bincount(v, weights=w), rtol=1e-6
            )


class TestCovGrid(TestCase):
    def test_cov_bias_ddof_combinations(self):
        rng = np.random.default_rng(66)
        m = rng.standard_normal((4, 5 * self.comm.size)).astype(np.float32)
        x = ht.array(m, split=1)
        np.testing.assert_allclose(
            ht.cov(x).numpy(), np.cov(m), rtol=1e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            ht.cov(x, bias=True).numpy(), np.cov(m, bias=True), rtol=1e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            ht.cov(x, ddof=0).numpy(), np.cov(m, ddof=0), rtol=1e-3, atol=1e-4
        )

    def test_cov_with_y(self):
        rng = np.random.default_rng(67)
        a = rng.standard_normal(3 * self.comm.size).astype(np.float32)
        b = 2 * a + rng.standard_normal(len(a)).astype(np.float32) * 0.1
        got = ht.cov(ht.array(a, split=0), ht.array(b, split=0))
        want = np.cov(a, b)
        np.testing.assert_allclose(got.numpy(), want, rtol=1e-2, atol=1e-3)


class TestMaximumMinimumGrid(TestCase):
    def test_pairwise_with_broadcast(self):
        p = self.comm.size
        a = np.arange((p + 1) * 3, dtype=np.float32).reshape(p + 1, 3)
        b = np.full(3, p * 1.5, dtype=np.float32)
        for split in (None, 0, 1):
            x = ht.array(a, split=split)
            self.assert_array_equal(ht.maximum(x, ht.array(b)), np.maximum(a, b))
            self.assert_array_equal(ht.minimum(x, ht.array(b)), np.minimum(a, b))

    def test_nan_propagation(self):
        a = np.asarray([1.0, np.nan, 3.0], dtype=np.float32)
        b = np.asarray([2.0, 2.0, 2.0], dtype=np.float32)
        got = ht.maximum(ht.array(a, split=0), ht.array(b, split=0)).numpy()
        assert np.isnan(got[1])


def _spy_percentile_fast_path():
    """Patch statistics._percentile_sorted_axis with a call counter;
    returns (counter, undo)."""
    from heat_tpu.core import statistics as st

    calls = []
    orig = st._percentile_sorted_axis

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    st._percentile_sorted_axis = spy
    return calls, lambda: setattr(st, "_percentile_sorted_axis", orig)


class TestDistributedPercentile(TestCase):
    """The split-axis fast path (statistics._percentile_sorted_axis, here
    via 1-D inputs): distributed sort + order-statistic gather — the data
    never replicates, unlike the reference's rank-0 gather
    (reference statistics.py:1406-1441)."""

    def _spy(self):
        return _spy_percentile_fast_path()

    @pytest.mark.slow
    def test_fast_path_taken_and_numpy_exact(self):
        rng = np.random.default_rng(71)
        a = rng.standard_normal(5 * self.comm.size + 3)
        x = ht.array(a, split=0)
        calls, undo = self._spy()
        try:
            for method in ("linear", "lower", "higher", "midpoint", "nearest"):
                for q in (0.0, 37.5, 100.0, [10, 50, 99.5], [[0, 25], [75, 100]]):
                    got = ht.percentile(x, q, interpolation=method).numpy()
                    want = np.percentile(a, q, method=method)
                    np.testing.assert_allclose(got, want, rtol=1e-12, err_msg=f"{method} {q}")
        finally:
            undo()
        if self.comm.size > 1:
            assert len(calls) == 25, "distributed fast path not taken"
        # replicated input must NOT take the sorted path
        calls2, undo2 = self._spy()
        try:
            ht.percentile(ht.array(a, split=None), 50)
        finally:
            undo2()
        assert not calls2

    def test_axis_forms_keepdims_and_median(self):
        rng = np.random.default_rng(72)
        a = rng.standard_normal(4 * self.comm.size + 1)
        x = ht.array(a, split=0)
        np.testing.assert_allclose(
            ht.percentile(x, 30, axis=0, keepdims=True).numpy(),
            np.percentile(a, 30, axis=0, keepdims=True),
        )
        np.testing.assert_allclose(
            ht.percentile(x, [30, 60], keepdims=True).numpy(),
            np.percentile(a, [30, 60], keepdims=True),
        )
        np.testing.assert_allclose(ht.median(x).numpy(), np.median(a))

    def test_nan_makes_every_percentile_nan(self):
        a = np.arange(3.0 * self.comm.size)
        a[1] = np.nan
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            got = ht.percentile(ht.array(a, split=0), [0, 50, 100]).numpy()
        assert np.isnan(got).all()

    def test_integer_input_and_out_param(self):
        rng = np.random.default_rng(73)
        a = rng.integers(-50, 50, 4 * self.comm.size + 2)
        x = ht.array(a, split=0)
        np.testing.assert_allclose(
            ht.percentile(x, [12.5, 88.0]).numpy(), np.percentile(a, [12.5, 88.0])
        )
        out = ht.zeros(2, dtype=ht.float64)
        r = ht.percentile(x, [25.0, 75.0], out=out)
        np.testing.assert_allclose(out.numpy(), np.percentile(a, [25.0, 75.0]))
        assert r is out

    def test_out_of_range_q_raises(self):
        x = ht.arange(3 * self.comm.size, split=0)
        with pytest.raises(ValueError):
            ht.percentile(x, 100.5)
        with pytest.raises(ValueError):
            ht.percentile(x, [-0.1, 50.0])

    def test_split_none_agreement(self):
        rng = np.random.default_rng(74)
        a = rng.standard_normal(6 * self.comm.size)
        qs = [5, 37, 50, 93]
        for method in ("linear", "nearest"):
            d = ht.percentile(ht.array(a, split=0), qs, interpolation=method).numpy()
            r = ht.percentile(ht.array(a, split=None), qs, interpolation=method).numpy()
            np.testing.assert_allclose(d, r, rtol=1e-9)

    def test_empty_q_and_nan_q(self):
        x = ht.arange(3 * self.comm.size, split=0)
        r = ht.percentile(x, [])
        assert r.shape == (0,)
        for bad in (float("nan"), [50.0, float("nan")]):
            with pytest.raises(ValueError):
                ht.percentile(x, bad)


class TestDistributedHistograms(TestCase):
    """bincount/histogram/histc as distributed algorithms: per-shard counts
    (pads carry weight 0) + one psum — the reference's local hist +
    Allreduce (statistics.py:375,:509) as a shard_map kernel. Any split
    axis works: binning is order-independent."""

    def test_bincount_grid(self):
        rng = np.random.default_rng(81)
        a = rng.integers(0, 11, 5 * self.comm.size + 3)
        w = rng.standard_normal(len(a))
        for split in (None, 0):
            x = ht.array(a, split=split)
            np.testing.assert_array_equal(ht.bincount(x).numpy(), np.bincount(a))
            np.testing.assert_array_equal(
                ht.bincount(x, minlength=25).numpy(), np.bincount(a, minlength=25)
            )
            np.testing.assert_allclose(
                ht.bincount(x, weights=ht.array(w, split=split)).numpy(),
                np.bincount(a, weights=w),
                rtol=1e-10,
            )
        # weights laid out differently from x get resplit, not mis-aligned
        np.testing.assert_allclose(
            ht.bincount(ht.array(a, split=0), weights=ht.array(w, split=None)).numpy(),
            np.bincount(a, weights=w),
            rtol=1e-10,
        )

    def test_bincount_negative_raises(self):
        with pytest.raises(ValueError):
            ht.bincount(ht.array(np.asarray([0, 1, -1]), split=0))

    @pytest.mark.slow
    def test_histogram_splits_bins_weights_density(self):
        rng = np.random.default_rng(82)
        t = rng.standard_normal((2 * self.comm.size + 1, 5))
        wt = rng.uniform(0.5, 2.0, t.shape)
        for split in (None, 0, 1):
            x = ht.array(t, split=split)
            for bins in (6, [-2.5, -1.0, 0.0, 0.25, 3.0]):
                hg, eg = ht.histogram(x, bins=bins)
                hn, en = np.histogram(t, bins=bins)
                np.testing.assert_allclose(hg.numpy(), hn, err_msg=f"{split} {bins}")
                np.testing.assert_allclose(eg.numpy(), en, rtol=1e-12)
            hg, _ = ht.histogram(x, bins=7, range=(-1.0, 1.25))
            hn, _ = np.histogram(t, bins=7, range=(-1.0, 1.25))
            np.testing.assert_allclose(hg.numpy(), hn)
            hg, _ = ht.histogram(x, bins=8, weights=ht.array(wt, split=split))
            hn, _ = np.histogram(t, bins=8, weights=wt)
            np.testing.assert_allclose(hg.numpy(), hn, rtol=1e-10)
            hg, _ = ht.histogram(x, bins=8, density=True)
            hn, _ = np.histogram(t, bins=8, density=True)
            np.testing.assert_allclose(hg.numpy(), hn, rtol=1e-10)

    def test_histc_range_and_autorange(self):
        rng = np.random.default_rng(83)
        t = rng.standard_normal(7 * self.comm.size + 2).astype(np.float32)
        for split in (None, 0):
            x = ht.array(t, split=split)
            got = ht.histc(x, bins=12, min=-1.0, max=1.0).numpy()
            want, _ = np.histogram(t, bins=12, range=(-1.0, 1.0))
            np.testing.assert_allclose(got, want.astype(np.float32))
            got = ht.histc(x, bins=9).numpy()
            want, _ = np.histogram(t, bins=9, range=(float(t.min()), float(t.max())))
            np.testing.assert_allclose(got, want.astype(np.float32))

    def test_f32_binning_consistent_across_paths(self):
        # f32 data: distributed and replicated paths must agree bin-for-bin
        # and both match numpy's EXACT-f64 binning (numpy's own f32 fast
        # path computes indices in f32 and can drift by O(1) counts on
        # edge-straddling values — that drift is numpy's, not ours)
        rng = np.random.default_rng(84)
        t = rng.standard_normal(4001 * self.comm.size).astype(np.float32)
        hd = ht.histogram(ht.array(t, split=0), bins=25, range=(-3, 3))[0].numpy()
        hr = ht.histogram(ht.array(t, split=None), bins=25, range=(-3, 3))[0].numpy()
        hn = np.histogram(t.astype(np.float64), bins=25, range=(-3, 3))[0]
        np.testing.assert_array_equal(hd, hr)
        np.testing.assert_array_equal(hd, hn)

    def test_raw_weights_on_padded_split(self):
        # non-DNDarray weights must pick up x's padding/sharding
        rng = np.random.default_rng(85)
        a = rng.integers(0, 6, 3 * self.comm.size + 1)
        w = rng.uniform(0.1, 1.0, len(a))
        got = ht.bincount(ht.array(a, split=0), weights=w).numpy()
        np.testing.assert_allclose(got, np.bincount(a, weights=w), rtol=1e-10)
        t = rng.standard_normal(5 * self.comm.size + 2)
        hg, _ = ht.histogram(ht.array(t, split=0), bins=6, weights=np.abs(t))
        hn, _ = np.histogram(t, bins=6, weights=np.abs(t))
        np.testing.assert_allclose(hg.numpy(), hn, rtol=1e-10)

    def test_degenerate_and_invalid_ranges(self):
        const = ht.array(np.full(2 * self.comm.size, 2.0), split=0)
        # lo == hi widens to (lo-.5, hi+.5) like numpy — all values counted
        assert float(ht.histc(const, bins=4).numpy().sum()) == const.size
        hg, eg = ht.histogram(const, bins=4)
        hn, en = np.histogram(const.numpy(), bins=4)
        np.testing.assert_array_equal(hg.numpy(), hn)
        np.testing.assert_allclose(eg.numpy(), en)
        with pytest.raises(ValueError):
            ht.histc(const, bins=4, min=5.0, max=1.0)
        with pytest.raises(ValueError):
            ht.histogram(const, bins=4, range=(2.0, -2.0))

    def test_nan_range_raises_like_numpy(self):
        bad = ht.array(np.asarray([1.0, np.nan]), split=0)
        with pytest.raises(ValueError):
            ht.histogram(bad, bins=4)  # auto-range sees NaN
        with pytest.raises(ValueError):
            ht.histc(bad, bins=4)
        with pytest.raises(ValueError):
            ht.histogram(bad, bins=4, range=(np.nan, np.nan))
        # explicit finite range: NaNs simply aren't counted, like numpy
        h, _ = ht.histogram(bad, bins=4, range=(0.0, 2.0))
        hn, _ = np.histogram(np.asarray([1.0, np.nan]), bins=4, range=(0.0, 2.0))
        np.testing.assert_array_equal(h.numpy(), hn)


class TestAxisPercentileDistributed(TestCase):
    """percentile along the SPLIT axis of n-D arrays: distributed sort per
    lane + replicated order-statistic slice gather — no logical gather."""

    @pytest.mark.slow
    def test_grid_vs_numpy(self):
        rng = np.random.default_rng(171)
        calls, undo = _spy_percentile_fast_path()
        try:
            for shape, split in (
                ((3 * self.comm.size + 1, 4), 0),
                ((3, 2 * self.comm.size + 3), 1),
            ):
                t = rng.standard_normal(shape)
                x = ht.array(t, split=split)
                for method in ("linear", "nearest", "midpoint", "lower", "higher"):
                    for q in (35.0, [10, 50, 99], [[5, 25], [75, 95]]):
                        for kd in (False, True):
                            got = ht.percentile(
                                x, q, axis=split, interpolation=method, keepdims=kd
                            ).numpy()
                            want = np.percentile(
                                t, q, axis=split, method=method, keepdims=kd
                            )
                            np.testing.assert_allclose(got, want, rtol=1e-12)
        finally:
            undo()
        if self.comm.size > 1:
            assert calls, "axis fast path not taken"

    def test_nan_lane_and_median(self):
        rng = np.random.default_rng(172)
        t = rng.standard_normal((4 * self.comm.size, 3))
        t[1, 1] = np.nan
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            got = ht.percentile(ht.array(t, split=0), 50, axis=0).numpy()
            want = np.percentile(t, 50, axis=0)
        np.testing.assert_allclose(got, want, equal_nan=True)
        t2 = rng.standard_normal((2 * self.comm.size + 1, 5))
        np.testing.assert_allclose(
            ht.median(ht.array(t2, split=0), axis=0).numpy(), np.median(t2, axis=0)
        )


class TestAverageSplitAxisWeights(TestCase):
    """1-D weights along the split axis align to x's chunking instead of
    replicating an axis-length vector — the weighted reduce stays
    shard-local until the final psum."""

    def test_no_gather_and_numpy_exact(self):
        from heat_tpu.core.dndarray import _PERF_STATS

        rng = np.random.default_rng(181)
        n = 4 * self.comm.size + 3
        t = rng.standard_normal((n, 3))
        w = rng.uniform(0.5, 2.0, n)
        for wsplit in (0, None):
            x = ht.array(t, split=0)
            c0 = _PERF_STATS["logical_slices"]
            avg, den = ht.average(
                x, axis=0, weights=ht.array(w, split=wsplit), returned=True
            )
            assert _PERF_STATS["logical_slices"] == c0
            np.testing.assert_allclose(
                avg.numpy(), np.average(t, axis=0, weights=w), rtol=1e-10
            )
            np.testing.assert_allclose(den.numpy(), np.full(3, w.sum()), rtol=1e-10)
