"""Correctness of the fused Pallas cdist kernel via the Pallas interpreter
(the TPU lowering shares the same kernel body; the on-TPU numerics are
additionally covered by the bench + the cdist suite when run on hardware).
Oracle: scipy-style direct computation in numpy."""

import numpy as np
import pytest

import jax.numpy as jnp

from heat_tpu.spatial.pallas_cdist import (
    cdist_precision,
    euclid_pallas,
    pallas_cdist_applicable,
)


def _np_cdist(x, y):
    return np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))


class TestEuclidPallasInterpret:
    @pytest.mark.parametrize(
        "m,n,k",
        [
            (16, 24, 8),      # tiny, everything sub-block
            (130, 257, 33),   # non-multiples everywhere
            (512, 512, 128),  # exact block multiples
        ],
    )
    def test_dist_matches_numpy(self, m, n, k):
        rng = np.random.default_rng(7)
        x = rng.standard_normal((m, k)).astype(np.float32)
        y = rng.standard_normal((n, k)).astype(np.float32)
        got = np.asarray(
            euclid_pallas(jnp.asarray(x), jnp.asarray(y), interpret=True)
        )
        np.testing.assert_allclose(got, _np_cdist(x, y), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("epilogue", ["dist", "rbf"])
    @pytest.mark.parametrize(
        "m,n",
        [
            (520, 1030),   # ragged last block on both axes
            (512, 1030),   # on the columns only
            (520, 1024),   # on the rows only
            (1024, 2048),  # on neither: two whole blocks an axis
        ],
    )
    def test_edge_blocks_write_the_result_at_its_own_shape(self, m, n, epilogue):
        # the kernel's output is (m, n) itself: the last block of an axis
        # that is no block multiple is ragged, and its valid part (the last
        # rows, the last lanes) must be as right as the interior
        k, gamma = 18, 0.05
        rng = np.random.default_rng(m + n)
        x = rng.standard_normal((m, k)).astype(np.float32)
        y = rng.standard_normal((n, k)).astype(np.float32)
        got = np.asarray(
            euclid_pallas(
                jnp.asarray(x), jnp.asarray(y), gamma, epilogue=epilogue,
                interpret=True,
            )
        )
        assert got.shape == (m, n) and got.dtype == np.float32
        # float64 GEMM form: no (m, n, k) broadcast temporary at these sizes
        x64, y64 = x.astype(np.float64), y.astype(np.float64)
        d2 = (x64**2).sum(1)[:, None] + (y64**2).sum(1)[None, :] - 2.0 * x64 @ y64.T
        d2 = np.maximum(d2, 0.0)
        want = np.exp(-gamma * d2) if epilogue == "rbf" else np.sqrt(d2)
        np.testing.assert_allclose(got[-8:], want[-8:], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            got[:, -128:], want[:, -128:], rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_self_distance_diagonal_zero(self):
        rng = np.random.default_rng(8)
        x = rng.standard_normal((65, 17)).astype(np.float32)
        got = np.asarray(euclid_pallas(jnp.asarray(x), jnp.asarray(x), interpret=True))
        # the default "bf16x3" strategy really performs its three-pass
        # split product in interpret mode too, so the diagonal carries
        # genuine bf16x3-class cancellation residue (~sqrt(3e-4) ≈ 2e-2 on
        # d2 ≈ 2k) — the SAME scale the XLA quadratic form's HIGH dot
        # leaves on hardware; only exact-f32 interpret runs land at ~2e-3
        np.testing.assert_allclose(np.diag(got), 0.0, atol=5e-2)
        np.testing.assert_allclose(got, got.T, rtol=1e-5, atol=1e-5)

    def test_rbf_epilogue(self):
        rng = np.random.default_rng(9)
        x = rng.standard_normal((40, 12)).astype(np.float32)
        y = rng.standard_normal((30, 12)).astype(np.float32)
        gamma = 0.37
        got = np.asarray(
            euclid_pallas(
                jnp.asarray(x), jnp.asarray(y), gamma, epilogue="rbf",
                interpret=True,
            )
        )
        want = np.exp(-gamma * _np_cdist(x, y) ** 2)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_sharded_wiring_on_mesh(self):
        # the shard_map decomposition used on multi-device TPU, exercised
        # on the CPU mesh via the interpreter: split=0 x, replicated y
        import heat_tpu as ht
        from heat_tpu.spatial.distance import _pallas_local

        comm = ht.get_comm()
        rng = np.random.default_rng(11)
        n_rows = 16 * comm.size + comm.size // 2  # ragged over the mesh
        xn = rng.standard_normal((n_rows, 9)).astype(np.float32)
        yn = rng.standard_normal((13, 9)).astype(np.float32)
        x = ht.array(xn, split=0)
        xbuf = x._masked(0)
        out = _pallas_local(
            comm, xbuf, jnp.asarray(yn), "dist", 0.0, interpret=True
        )
        # each chip writes its (rows / p, n) slab at that shape: the columns
        # are y's 13, not a lane-padded 128
        assert out.shape == (xbuf.shape[0], 13)
        got = np.asarray(out)[:n_rows]  # physical pad rows sliced off
        np.testing.assert_allclose(got, _np_cdist(xn, yn), rtol=2e-4, atol=2e-4)

    def test_applicability_gate(self, monkeypatch):
        import jax

        import heat_tpu.spatial.pallas_cdist as mod

        # off-TPU: never applicable (interpret mode would be a de-opt)
        monkeypatch.setattr(mod.jax, "default_backend", lambda: "cpu")
        assert not pallas_cdist_applicable(128, jnp.float32)
        # on TPU: k and dtype gates decide
        monkeypatch.setattr(mod.jax, "default_backend", lambda: "tpu")
        assert pallas_cdist_applicable(128, jnp.float32)
        assert not pallas_cdist_applicable(1024, jnp.float32)  # k > _MAX_K
        assert not pallas_cdist_applicable(128, jnp.bfloat16)  # dtype gate

    @pytest.mark.parametrize("prec", ["DEFAULT", "HIGH", "HIGHEST", "bf16x3"])
    def test_precision_kwarg_wiring(self, prec):
        # wiring smoke test: each strategy must trace/jit through the
        # static kwarg and still produce the oracle result. The enum tiers
        # run as exact f32 in interpret mode (their on-chip numerics are a
        # tpu_tune.py concern; DEFAULT is documented-unsafe for the cdist
        # diagonal, distance.py:36-39), while "bf16x3" genuinely performs
        # its split product here — off-diagonal error stays ~1e-5 relative
        import jax

        rng = np.random.default_rng(3)
        x = rng.standard_normal((65, 17)).astype(np.float32)
        y = rng.standard_normal((33, 17)).astype(np.float32)
        out = euclid_pallas(
            jnp.asarray(x), jnp.asarray(y), interpret=True, precision=prec,
        )
        np.testing.assert_allclose(
            np.asarray(out), _np_cdist(x, y), rtol=2e-4, atol=2e-4
        )

    def test_precision_env_override(self, monkeypatch):
        # HEAT_TPU_CDIST_PREC flips the default strategy with no source
        # edit (advisor r5: bf16x3 is unmeasured on hardware; the revert
        # must be a flag — docs/TUNING_RUNBOOK.md)
        monkeypatch.delenv("HEAT_TPU_CDIST_PREC", raising=False)
        assert cdist_precision() == "bf16x3"
        monkeypatch.setenv("HEAT_TPU_CDIST_PREC", "highest")
        assert cdist_precision() == "HIGHEST"
        monkeypatch.setenv("HEAT_TPU_CDIST_PREC", "high")
        assert cdist_precision() == "HIGH"
        # an unknown value warns and keeps the safe default
        monkeypatch.setenv("HEAT_TPU_CDIST_PREC", "bf16x9")
        with pytest.warns(UserWarning, match="HEAT_TPU_CDIST_PREC"):
            assert cdist_precision() == "bf16x3"

    def test_precision_env_reaches_kernel(self, monkeypatch):
        # the resolved override must flow into the kernel and still hit
        # the oracle (HIGHEST runs as exact f32 in interpret mode)
        monkeypatch.setenv("HEAT_TPU_CDIST_PREC", "highest")
        rng = np.random.default_rng(11)
        x = rng.standard_normal((33, 17)).astype(np.float32)
        y = rng.standard_normal((21, 17)).astype(np.float32)
        got = np.asarray(
            euclid_pallas(jnp.asarray(x), jnp.asarray(y), interpret=True)
        )
        np.testing.assert_allclose(got, _np_cdist(x, y), rtol=2e-4, atol=2e-4)
