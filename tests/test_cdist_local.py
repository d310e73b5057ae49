"""The local distance program behind ``ht.spatial.cdist`` / ``rbf``: results
of the public calls against float64 numpy, and that there is one such
program — ``_local_dist``, the name the benchmark reads — whatever the
backend, the feature count, the epilogue and the layout of x."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import _knobs as knobs
from heat_tpu.spatial import distance


def _np_cdist(x, y):
    return np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))


def _d2_64(x, y):
    """float64 GEMM form: no (m, n, k) broadcast temporary."""
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    d2 = (x64**2).sum(1)[:, None] + (y64**2).sum(1)[None, :] - 2.0 * x64 @ y64.T
    return np.maximum(d2, 0.0)


def _pair(seed, m, n, k):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((m, k)).astype(np.float32),
        rng.standard_normal((n, k)).astype(np.float32),
    )


@pytest.mark.parametrize(
    "m,n,k",
    [
        (16, 24, 8),      # tiny
        (130, 257, 33),   # non-multiples of any tile everywhere
        (512, 512, 128),  # tile multiples
    ],
)
def test_dist_matches_numpy(m, n, k):
    x, y = _pair(7, m, n, k)
    got = ht.spatial.cdist(ht.array(x), ht.array(y), quadratic_expansion=True)
    np.testing.assert_allclose(got.numpy(), _np_cdist(x, y), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("epilogue", ["dist", "rbf"])
@pytest.mark.parametrize(
    "m,n",
    [
        (520, 1030),   # ragged on both axes
        (512, 1030),   # on the columns only
        (520, 1024),   # on the rows only
        (1024, 2048),  # on neither
    ],
)
def test_result_at_its_own_shape(m, n, epilogue):
    # the last rows and the last lanes are as right as the interior
    k, sigma = 18, np.sqrt(10.0)
    x, y = _pair(m + n, m, n, k)
    xs, ys = ht.array(x, split=0), ht.array(y)
    if epilogue == "rbf":
        got = ht.spatial.rbf(xs, ys, sigma=sigma, quadratic_expansion=True)
        want = np.exp(-_d2_64(x, y) / (2.0 * sigma * sigma))
    else:
        got = ht.spatial.cdist(xs, ys, quadratic_expansion=True)
        want = np.sqrt(_d2_64(x, y))
    got = got.numpy()
    assert got.shape == (m, n) and got.dtype == np.float32
    np.testing.assert_allclose(got[-8:], want[-8:], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got[:, -128:], want[:, -128:], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "quadratic,shape,atol",
    [
        # the difference form is exact on the diagonal up to f32 rounding
        (False, lambda p: (2 * p + 1, 5), 1e-3),
        # the GEMM form cancels ‖x‖² + ‖x‖² − 2‖x‖²: the residue of a
        # three-pass bf16 product (~sqrt(3e-4) at d2 ≈ 2k) bounds it on a chip
        (True, lambda p: (65, 17), 5e-2),
    ],
)
def test_self_distance_zero_diagonal(quadratic, shape, atol):
    rng = np.random.default_rng(9)
    x = rng.standard_normal(shape(ht.get_comm().size)).astype(np.float32)
    d = ht.spatial.cdist(ht.array(x, split=0), quadratic_expansion=quadratic).numpy()
    np.testing.assert_allclose(np.diag(d), 0.0, atol=atol)
    np.testing.assert_allclose(d, d.T, atol=1e-3)


def test_rbf_epilogue():
    x, y = _pair(9, 40, 30, 12)
    gamma = 0.37
    got = ht.spatial.rbf(
        ht.array(x), ht.array(y), sigma=np.sqrt(0.5 / gamma), quadratic_expansion=True
    )
    want = np.exp(-gamma * _np_cdist(x, y) ** 2)
    np.testing.assert_allclose(got.numpy(), want, rtol=2e-4, atol=2e-4)


def test_split_rows_on_the_mesh():
    # x split over the chips with a ragged tail, y whole on each: every
    # chip writes its (rows / p, n) slab, the columns are y's 13
    p = ht.get_comm().size
    n_rows = 16 * p + p // 2
    xn, yn = _pair(11, n_rows, 13, 9)
    out = ht.spatial.cdist(ht.array(xn, split=0), ht.array(yn), quadratic_expansion=True)
    assert out.split == 0 and out.shape == (n_rows, 13)
    assert out.larray.shape[1] == 13
    np.testing.assert_allclose(out.numpy(), _np_cdist(xn, yn), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quadratic", [True, False], ids=["quadratic", "pairwise"])
def test_local_rbf_is_one_program(monkeypatch, quadratic):
    # the epilogue is inside ``_local_dist``: two sigmas launch one compiled
    # program between them, and the ring's second pass is never called
    def second_pass(*_):
        raise AssertionError("the local rbf ran _rbf_from_dist")

    monkeypatch.setattr(distance, "_rbf_from_dist", second_pass)
    xn, yn = _pair(12, 37 + quadratic, 41, 6)  # shapes no other test launches
    x, y = ht.array(xn, split=0), ht.array(yn)
    before = distance._local_dist._cache_size()
    for sigma in (1.5, 3.0):
        got = ht.spatial.rbf(x, y, sigma=sigma, quadratic_expansion=quadratic)
        want = np.exp(-_d2_64(xn, yn) / (2.0 * sigma * sigma))
        np.testing.assert_allclose(got.numpy(), want, rtol=2e-4, atol=2e-4)
    assert distance._local_dist._cache_size() == before + 1


def test_only_the_ring_applies_the_epilogue_as_a_pass(monkeypatch):
    # the ring launch keeps ``_rbf_from_dist`` over its result; the local
    # launch of the same call has it inside the program, and they agree
    calls = []
    second_pass = distance._rbf_from_dist
    monkeypatch.setattr(
        distance, "_rbf_from_dist",
        lambda d, gamma: calls.append(d.shape) or second_pass(d, gamma),
    )
    p = ht.get_comm().size
    xn, yn = _pair(17, 4 * p, 6 * p, 7)
    x, y = ht.array(xn, split=0), ht.array(yn, split=0)
    local = ht.spatial.rbf(x, y, sigma=2.0, quadratic_expansion=True)
    assert calls == []
    ring = ht.spatial.rbf(x, y, sigma=2.0, quadratic_expansion=True, ring=True)
    assert len(calls) == (1 if p > 1 else 0)
    np.testing.assert_allclose(local.numpy(), ring.numpy(), rtol=1e-6, atol=1e-6)


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """What a gate on the backend would see on the chip. The arrays are made
    first: only the distance call runs under it."""
    def apply():
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return apply


@pytest.fixture
def launches(monkeypatch):
    """Names of the jitted functions the distance engine launches."""
    seen = []
    local = distance._local_dist

    def counted(*args):
        seen.append(local.lower(*args).as_text().split("\n", 1)[0])
        return local(*args)

    monkeypatch.setattr(distance, "_local_dist", counted)
    return seen


def test_every_feature_count_launches_local_dist(as_if_on_tpu, launches):
    # no gate on backend, k or dtype: 18 features and 600 take the one
    # program, by the name the benchmark's roofline looks for
    pairs = [_pair(13, 24, 16, k) for k in (18, 600)]
    arrays = [(ht.array(x, split=0), ht.array(y)) for x, y in pairs]
    as_if_on_tpu()
    for (x, y), (xn, yn) in zip(arrays, pairs):
        got = ht.spatial.cdist(x, y, quadratic_expansion=True)
        np.testing.assert_allclose(got.numpy(), _np_cdist(xn, yn), rtol=2e-4, atol=2e-4)
    assert len(launches) == 2 and all("@jit__local_dist " in m for m in launches)


def test_replicated_and_split_x_agree(as_if_on_tpu, launches):
    p = ht.get_comm().size
    xn, yn = _pair(14, 8 * p + 3, 21, 18)
    whole, split, y = ht.array(xn), ht.array(xn, split=0), ht.array(yn, split=0)
    as_if_on_tpu()
    a = ht.spatial.cdist(whole, y, quadratic_expansion=True)
    b = ht.spatial.cdist(split, y, quadratic_expansion=True)
    assert (a.split, b.split) == (None, 0)
    assert len(launches) == 2
    np.testing.assert_allclose(a.numpy(), b.numpy(), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(b.numpy(), _np_cdist(xn, yn), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quadratic", [True, False], ids=["quadratic", "pairwise"])
def test_rbf_diagonal_is_one_at_a_ragged_shape(quadratic):
    p = ht.get_comm().size
    xn, _ = _pair(15, 16 * p + 5, 1, 18)
    kern = ht.spatial.rbf(ht.array(xn, split=0), sigma=8.0, quadratic_expansion=quadratic)
    got = kern.numpy()
    assert got.shape == (xn.shape[0],) * 2
    # gamma = 1/128 times a d2 residue of a few f32 ulps of 2‖x‖² ≈ 36
    np.testing.assert_allclose(np.diag(got), 1.0, atol=1e-6)
    assert got.max() <= 1.0 + 1e-6


def test_cdist_prec_knob_is_gone(monkeypatch):
    assert "HEAT_TPU_CDIST_PREC" not in knobs.REGISTRY
    xn, yn = _pair(16, 33, 21, 17)
    x, y = ht.array(xn), ht.array(yn)
    base = ht.spatial.cdist(x, y, quadratic_expansion=True).numpy()
    # the product's precision is the code's constant: the name selects nothing
    monkeypatch.setenv("HEAT_TPU_CDIST_PREC", "default")
    again = ht.spatial.cdist(x, y, quadratic_expansion=True).numpy()
    np.testing.assert_array_equal(base, again)
    text = distance._local_dist.lower(
        distance._quadratic_euclidean, x.larray, y.larray, jnp.float32
    ).as_text()
    assert "HIGH" in text and "DEFAULT" not in text


def test_spatial_has_no_kernel_module():
    import importlib.util

    assert importlib.util.find_spec("heat_tpu.spatial.pallas_cdist") is None
    assert not hasattr(ht.spatial, "pallas_cdist")
    assert sorted(distance.__all__) == ["cdist", "manhattan", "rbf"]
