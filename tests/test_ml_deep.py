"""Deep ML-algorithm checks — estimator-contract sweeps (get/set params,
refit idempotence, split invariance of predictions), spatial-kernel
equivalences, and oracle comparisons against closed-form results
(reference heat/cluster|regression|naive_bayes/tests drive the same
sklearn-style contracts per rank)."""

import numpy as np
import pytest

import heat_tpu as ht
from .basic_test import TestCase


def blobs(p, n_per=12, d=4, k=3, seed=0, spread=8.0):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((k, d)).astype(np.float32) * spread
    pts = np.concatenate(
        [centers[i] + rng.standard_normal((n_per, d)).astype(np.float32) for i in range(k)]
    )
    labels = np.repeat(np.arange(k), n_per)
    perm = rng.permutation(len(pts))
    return pts[perm], labels[perm], centers


class TestEstimatorContract(TestCase):
    """BaseEstimator get_params/set_params round-trips (reference
    core/base.py contract) for every estimator family."""

    def _roundtrip(self, est):
        params = est.get_params()
        assert isinstance(params, dict) and params
        est.set_params(**params)
        assert est.get_params() == params

    def test_kmeans_params(self):
        self._roundtrip(ht.cluster.KMeans(n_clusters=4, max_iter=7))

    def test_kmedians_params(self):
        self._roundtrip(ht.cluster.KMedians(n_clusters=2))

    def test_kmedoids_params(self):
        self._roundtrip(ht.cluster.KMedoids(n_clusters=2))

    def test_lasso_params(self):
        self._roundtrip(ht.regression.Lasso(lam=0.05, max_iter=20))

    def test_gnb_params(self):
        self._roundtrip(ht.naive_bayes.GaussianNB())

    def test_knn_params(self):
        self._roundtrip(ht.classification.KNeighborsClassifier(n_neighbors=3))

    def test_set_params_unknown_key_raises(self):
        est = ht.cluster.KMeans()
        with pytest.raises((ValueError, TypeError)):
            est.set_params(definitely_not_a_param=1)


class TestSplitInvariance(TestCase):
    """Fitting on split vs replicated data must give the same model —
    the core promise of the framework (SURVEY §2.4: 'pure ht-ops →
    automatically distributed')."""

    def test_kmeans_split_invariant(self):
        pts, _, _ = blobs(self.comm.size, seed=1)
        m_rep = ht.cluster.KMeans(n_clusters=3, init="random", random_state=5, max_iter=30)
        m_rep.fit(ht.array(pts, split=None))
        m_split = ht.cluster.KMeans(n_clusters=3, init="random", random_state=5, max_iter=30)
        m_split.fit(ht.array(pts, split=0))
        np.testing.assert_allclose(
            np.sort(m_rep.cluster_centers_.numpy(), axis=0),
            np.sort(m_split.cluster_centers_.numpy(), axis=0),
            rtol=1e-4, atol=1e-4,
        )

    def test_gnb_split_invariant(self):
        pts, labels, _ = blobs(self.comm.size, seed=2)
        preds = []
        for split in (None, 0):
            m = ht.naive_bayes.GaussianNB()
            m.fit(ht.array(pts, split=split), ht.array(labels, split=split))
            preds.append(m.predict(ht.array(pts, split=split)).numpy())
        np.testing.assert_array_equal(preds[0], preds[1])

    def test_lasso_split_invariant(self):
        rng = np.random.default_rng(3)
        n, d = 8 * self.comm.size, 6
        X = rng.standard_normal((n, d)).astype(np.float32)
        w = np.zeros(d, dtype=np.float32)
        w[:2] = [2.0, -3.0]
        y = X @ w
        coefs = []
        for split in (None, 0):
            m = ht.regression.Lasso(lam=0.01, max_iter=200)
            m.fit(ht.array(X, split=split), ht.array(y[:, None], split=split))
            coefs.append(np.asarray(m.theta.numpy()).ravel())
        np.testing.assert_allclose(coefs[0], coefs[1], rtol=1e-4, atol=1e-4)

    def test_knn_split_invariant(self):
        pts, labels, _ = blobs(self.comm.size, seed=4)
        preds = []
        for split in (None, 0):
            m = ht.classification.KNeighborsClassifier(n_neighbors=3)
            m.fit(ht.array(pts, split=split), ht.array(labels, split=split))
            preds.append(m.predict(ht.array(pts, split=split)).numpy())
        np.testing.assert_array_equal(preds[0], preds[1])


class TestKMeansDeep(TestCase):
    def test_plusplus_init_beats_degenerate(self):
        pts, _, centers = blobs(self.comm.size, n_per=20, k=3, seed=5)
        m = ht.cluster.KMeans(n_clusters=3, init="kmeans++", random_state=0, max_iter=50)
        m.fit(ht.array(pts, split=0))
        got = np.sort(m.cluster_centers_.numpy(), axis=0)
        want = np.sort(centers, axis=0)
        # every true center recovered within the blob radius
        assert np.abs(got - want).max() < 2.5

    def test_predict_assigns_nearest(self):
        pts, _, _ = blobs(self.comm.size, seed=6)
        m = ht.cluster.KMeans(n_clusters=3, random_state=1, max_iter=30)
        m.fit(ht.array(pts, split=0))
        labels = m.predict(ht.array(pts, split=0)).numpy().ravel()
        c = m.cluster_centers_.numpy()
        d = ((pts[:, None, :] - c[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(labels, d.argmin(1))

    def test_functional_value_decreases_with_iters(self):
        pts, _, _ = blobs(self.comm.size, seed=7)
        x0 = ht.array(pts, split=0)

        def inertia(model):
            c = model.cluster_centers_.numpy()
            d = ((pts[:, None, :] - c[None]) ** 2).sum(-1)
            return d.min(1).sum()

        m1 = ht.cluster.KMeans(n_clusters=3, init="random", random_state=9, max_iter=1)
        m1.fit(x0)
        m20 = ht.cluster.KMeans(n_clusters=3, init="random", random_state=9, max_iter=20)
        m20.fit(x0)
        assert inertia(m20) <= inertia(m1) + 1e-3

    def test_n_clusters_one(self):
        pts, _, _ = blobs(self.comm.size, seed=8)
        m = ht.cluster.KMeans(n_clusters=1, max_iter=10)
        m.fit(ht.array(pts, split=0))
        np.testing.assert_allclose(
            m.cluster_centers_.numpy().ravel(), pts.mean(0), rtol=1e-3, atol=1e-3
        )


class TestSpatialDeep(TestCase):
    def test_cdist_xy_asymmetric_shapes(self):
        rng = np.random.default_rng(10)
        x = rng.standard_normal((self.comm.size + 2, 4)).astype(np.float32)
        y = rng.standard_normal((7, 4)).astype(np.float32)
        want = np.sqrt(((x[:, None] - y[None]) ** 2).sum(-1))
        for sx in (None, 0):
            for sy in (None, 0):
                got = ht.spatial.cdist(ht.array(x, split=sx), ht.array(y, split=sy))
                np.testing.assert_allclose(got.numpy(), want, rtol=1e-3, atol=1e-3)

    def test_quadratic_vs_exact_agree(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal((10, 3)).astype(np.float32)
        exact = ht.spatial.cdist(ht.array(x, split=0)).numpy()
        quad = ht.spatial.cdist(ht.array(x, split=0), quadratic_expansion=True).numpy()
        np.testing.assert_allclose(exact, quad, rtol=1e-2, atol=1e-2)

    def test_manhattan_oracle(self):
        rng = np.random.default_rng(12)
        x = rng.standard_normal((self.comm.size + 1, 3)).astype(np.float32)
        want = np.abs(x[:, None] - x[None]).sum(-1)
        got = ht.spatial.manhattan(ht.array(x, split=0)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_rbf_kernel_properties(self):
        rng = np.random.default_rng(13)
        x = rng.standard_normal((8, 3)).astype(np.float32)
        k = ht.spatial.rbf(ht.array(x, split=0), sigma=2.0).numpy()
        np.testing.assert_allclose(np.diag(k), 1.0, atol=1e-4)
        assert (k > 0).all() and (k <= 1 + 1e-6).all()

    def test_ring_vs_gemm_path_identical(self):
        rng = np.random.default_rng(14)
        n = 4 * self.comm.size
        x = rng.standard_normal((n, 4)).astype(np.float32)
        a = ht.spatial.cdist(ht.array(x, split=0), ring=False).numpy()
        b = ht.spatial.cdist(ht.array(x, split=0), ring=True).numpy()
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


class TestGaussianNBDeep(TestCase):
    def test_proba_rows_sum_to_one(self):
        pts, labels, _ = blobs(self.comm.size, seed=15)
        m = ht.naive_bayes.GaussianNB()
        m.fit(ht.array(pts, split=0), ht.array(labels, split=0))
        proba = m.predict_proba(ht.array(pts, split=0)).numpy()
        np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-4)

    def test_partial_fit_matches_full_fit(self):
        pts, labels, _ = blobs(self.comm.size, n_per=16, seed=16)
        full = ht.naive_bayes.GaussianNB()
        full.fit(ht.array(pts, split=0), ht.array(labels, split=0))
        inc = ht.naive_bayes.GaussianNB()
        half = len(pts) // 2
        classes = ht.array(np.unique(labels))
        inc.partial_fit(
            ht.array(pts[:half], split=0), ht.array(labels[:half], split=0), classes=classes
        )
        inc.partial_fit(ht.array(pts[half:], split=0), ht.array(labels[half:], split=0))
        np.testing.assert_allclose(
            full.theta_.numpy(), inc.theta_.numpy(), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            full.var_.numpy(), inc.var_.numpy(), rtol=1e-3, atol=1e-5
        )

    def test_priors_override(self):
        pts, labels, _ = blobs(self.comm.size, k=2, seed=17)
        labels = labels % 2
        m = ht.naive_bayes.GaussianNB(priors=ht.array(np.asarray([0.9, 0.1], dtype=np.float32)))
        m.fit(ht.array(pts, split=0), ht.array(labels, split=0))
        np.testing.assert_allclose(m.class_prior_.numpy(), [0.9, 0.1], rtol=1e-5)


class TestLassoDeep(TestCase):
    def test_soft_threshold_kills_small_coeffs(self):
        rng = np.random.default_rng(18)
        n, d = 10 * self.comm.size, 8
        X = rng.standard_normal((n, d)).astype(np.float32)
        w = np.zeros(d, dtype=np.float32)
        w[0] = 5.0
        y = X @ w
        m = ht.regression.Lasso(lam=0.5, max_iter=300)
        m.fit(ht.array(X, split=0), ht.array(y[:, None], split=0))
        coef = np.asarray(m.theta.numpy()).ravel()[1:]  # drop intercept row
        assert np.abs(coef[0]) > 1.0  # true signal survives
        assert np.abs(coef[1:]).max() < 0.3  # noise coordinates shrunk

    def test_lam_zero_reduces_to_least_squares(self):
        rng = np.random.default_rng(19)
        n, d = 12 * self.comm.size, 3
        X = rng.standard_normal((n, d)).astype(np.float32)
        w = np.asarray([1.0, -2.0, 0.5], dtype=np.float32)
        y = X @ w
        m = ht.regression.Lasso(lam=1e-6, max_iter=500, tol=1e-12)
        m.fit(ht.array(X, split=0), ht.array(y[:, None], split=0))
        coef = np.asarray(m.theta.numpy()).ravel()[1:]
        np.testing.assert_allclose(coef, w, rtol=1e-2, atol=1e-2)


class TestLaplacianDeep(TestCase):
    def test_row_sums_zero_unnormalized(self):
        rng = np.random.default_rng(20)
        x = rng.standard_normal((2 * self.comm.size, 3)).astype(np.float32)
        lap = ht.graph.Laplacian(
            lambda a: ht.spatial.rbf(a, sigma=1.0), definition="simple",
            mode="fully_connected",
        )
        L = lap.construct(ht.array(x, split=0)).numpy()
        np.testing.assert_allclose(L.sum(axis=1), 0.0, atol=1e-3)

    def test_symmetric_normalized_diagonal_ones(self):
        rng = np.random.default_rng(21)
        x = rng.standard_normal((2 * self.comm.size, 3)).astype(np.float32)
        lap = ht.graph.Laplacian(
            lambda a: ht.spatial.rbf(a, sigma=1.0), definition="norm_sym",
            mode="fully_connected",
        )
        L = lap.construct(ht.array(x, split=0)).numpy()
        np.testing.assert_allclose(np.diag(L), 1.0, atol=1e-3)
