"""Correctness of the fused Pallas Lloyd kernel via the Pallas interpreter:
the full pallas fit must agree with the XLA `_lloyd_fit` (same centers,
labels, inertia) from the same start — they implement the same math."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from heat_tpu.cluster.kmeans import _lloyd_final, _lloyd_fit
from heat_tpu.cluster.pallas_lloyd import (
    _lloyd_operands,
    lloyd_fit_pallas,
    lloyd_fit_pallas_sharded,
    lloyd_form,
)


def _blobs(n, d, k, seed):
    rng = np.random.default_rng(seed)
    protos = rng.standard_normal((k, d)).astype(np.float32) * 6.0
    lab = rng.integers(0, k, n)
    return (protos[lab] + rng.standard_normal((n, d)).astype(np.float32)), protos


class TestPallasLloydInterpret:
    def _agree(self, n, d, k, pad_rows, seed, block_m=64):
        x, protos = _blobs(n, d, k, seed)
        # emulate the tail-pad invariant: pad rows are zeros, weights drop them
        xp = np.vstack([x, np.zeros((pad_rows, d), np.float32)])
        w = (np.arange(n + pad_rows) < n).astype(np.float32)
        c0 = x[:k].copy()

        want_c, want_l, want_i, want_it = _lloyd_fit(
            jnp.asarray(xp), jnp.asarray(w), jnp.asarray(c0), 20, jnp.float32(0.0)
        )
        got_c, got_l, got_i, got_it = lloyd_fit_pallas(
            jnp.asarray(xp), jnp.asarray(c0), n, 20, jnp.float32(0.0),
            block_m=block_m, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(got_l)[:n], np.asarray(want_l)[:n]
        )
        np.testing.assert_allclose(float(got_i), float(want_i), rtol=1e-3)

    def test_small_blocked(self):
        # several row blocks, ragged tail pad, k and d far from tile sizes
        self._agree(n=300, d=5, k=7, pad_rows=20, seed=0)

    def test_k_above_lanes(self):
        self._agree(n=257, d=3, k=9, pad_rows=7, seed=1)

    def test_no_padding_needed(self):
        self._agree(n=256, d=8, k=4, pad_rows=0, seed=2, block_m=128)

    def test_sharded_fit_on_mesh(self):
        # the multi-device shard_map + per-iteration psum wiring, on the
        # CPU mesh via the interpreter — must agree with the XLA fit
        import heat_tpu as ht
        from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas_sharded

        comm = ht.get_comm()
        n, d, k = 40 * comm.size + 3, 4, 5
        # STRONGLY separated blobs: the kernel scores with c2 - 2xc (no
        # x2 term) which can flip last-ulp near-ties vs the XLA d2 form —
        # with centroids 60 apart and noise 1 no assignment is ambiguous
        rng = np.random.default_rng(7)
        protos = (rng.permutation(k)[:, None] * 60.0 + rng.standard_normal((k, d))).astype(np.float32)
        lab = rng.integers(0, k, n)
        x = (protos[lab] + rng.standard_normal((n, d))).astype(np.float32)
        xd = ht.array(x, split=0)
        xb = xd._masked(0)  # padded sharded buffer, pads zeroed
        m = xb.shape[0]
        w = (np.arange(m) < n).astype(np.float32)
        c0 = (protos + 0.1).astype(np.float32)  # unambiguous from step one

        # one iteration from identical centers: the psum-merged sums/counts
        # must reproduce the XLA update (reduction-order tolerance only)
        want_c, _, _, _ = _lloyd_fit(
            jnp.asarray(np.pad(x, ((0, m - n), (0, 0)))), jnp.asarray(w),
            jnp.asarray(c0), 1, jnp.float32(0.0),
        )
        got_c, _, _, _ = lloyd_fit_pallas_sharded(
            comm, xb, jnp.asarray(c0), n, 1, jnp.float32(0.0),
            block_m=16, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-4)
        # to convergence: trajectories may flip boundary points (different
        # reduction order), but the fit quality must match
        want_c, _, want_i, _ = _lloyd_fit(
            jnp.asarray(np.pad(x, ((0, m - n), (0, 0)))), jnp.asarray(w),
            jnp.asarray(c0), 15, jnp.float32(0.0),
        )
        got_c, got_l, got_i, _ = lloyd_fit_pallas_sharded(
            comm, xb, jnp.asarray(c0), n, 15, jnp.float32(0.0),
            block_m=16, interpret=True,
        )
        assert abs(float(got_i) - float(want_i)) <= 0.02 * float(want_i) + 1e-3
        assert np.asarray(got_l)[:n].shape == (n,)

    def test_empty_cluster_keeps_center(self):
        # a far-away initial center captures nothing; both paths must keep it
        x = np.vstack([
            np.zeros((50, 2), np.float32),
            np.ones((50, 2), np.float32) * 2.0,
        ])
        c0 = np.array([[0.0, 0.0], [2.0, 2.0], [100.0, 100.0]], np.float32)
        got_c, got_l, _, _ = lloyd_fit_pallas(
            jnp.asarray(x), jnp.asarray(c0), 100, 5, jnp.float32(0.0),
            block_m=32, interpret=True,
        )
        want_c, want_l, _, _ = _lloyd_fit(
            jnp.asarray(x), jnp.ones((100,), jnp.float32), jnp.asarray(c0),
            5, jnp.float32(0.0),
        )
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))

    def test_precision_kwarg_wiring(self):
        # wiring smoke test: each strategy must trace/jit through the
        # static kwarg and reproduce the XLA fit oracle. The enum tiers
        # run as exact f32 in interpret mode (on-chip tier numerics are a
        # tpu_tune.py concern); "bf16x3" genuinely performs its split
        # product here, perturbing scores by ~1e-4 — so the fixture is
        # well-separated blobs (gap >> perturbation: no assignment can
        # flip) and the tolerance covers split-product center rounding
        import jax

        rng = np.random.default_rng(5)
        blobs = np.concatenate([
            rng.standard_normal((30, 6)).astype(np.float32) * 0.1 + 8.0 * c
            for c in range(4)
        ])
        c0 = blobs[::30].copy()  # one seed per blob
        ref_c, _, _, _ = _lloyd_fit(
            jnp.asarray(blobs), jnp.ones((120,), jnp.float32),
            jnp.asarray(c0), 8, jnp.float32(0.0),
        )
        for prec in (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST,
                     'bf16x3'):
            got_c, _, _, _ = lloyd_fit_pallas(
                jnp.asarray(blobs), jnp.asarray(c0), 120, 8,
                jnp.float32(0.0), block_m=32, interpret=True, precision=prec,
            )
            np.testing.assert_allclose(
                np.asarray(got_c), np.asarray(ref_c), rtol=2e-4, atol=2e-3
            )


class TestLloydForms:
    """The two orientations of the block walk: feature-major where
    ``d % 128 != 0`` (clusters on sublanes, ``k`` rounded to 8), the
    row-major kernel where X arrives row-major."""

    @pytest.mark.parametrize(
        "n,d,k,block,form",
        [
            (4096, 64, 8, 512, "feature_major"),  # the benchmark's shape
            (300, 5, 7, 128, "feature_major"),  # ragged: 300 rows, blocks of 128
            (1000, 100, 3, 256, "feature_major"),  # d over 64, not a tile
            (2048, 64, 130, 512, "feature_major"),  # k over 128: 17 sublane tiles
            (257, 18, 9, None, "feature_major"),  # the form's own block, SUSY's width
            (512, 128, 8, 128, "row_major"),
            (512, 256, 8, 64, "row_major"),
            (200, 128, 130, None, "row_major"),  # two lane tiles of clusters
        ],
    )
    def test_agrees_with_the_xla_fit(self, n, d, k, block, form):
        assert lloyd_form(d) == form
        x, protos = _blobs(n, d, k, seed=n + d + k)
        c0 = (protos + 0.25).astype(np.float32)  # one start in each blob
        want_c, want_l, want_i, want_it = _lloyd_fit(
            jnp.asarray(x), jnp.ones((n,), jnp.float32), jnp.asarray(c0),
            6, jnp.float32(-1.0),
        )
        got_c, got_l, got_i, got_it = lloyd_fit_pallas(
            jnp.asarray(x), jnp.asarray(c0), n, 6, jnp.float32(-1.0),
            block_m=block, interpret=True,
        )
        assert int(got_it) == int(want_it) == 6
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
        np.testing.assert_allclose(float(got_i), float(want_i), rtol=1e-3)

    @pytest.mark.parametrize("d", [5, 8, 18, 64, 96, 100, 127, 129, 192])
    def test_narrow_and_odd_widths_go_feature_major(self, d):
        x = jnp.zeros((300, d), jnp.float32)
        xk, c0, block, feature_major = _lloyd_operands(x, x[:9], None)
        assert lloyd_form(d) == "feature_major" and feature_major
        # X.T, rows padded to whole lane tiles, no feature padded; k to 8
        assert xk.shape == (d, 384) and block == 384 and c0.shape == (16, d)

    @pytest.mark.parametrize("d", [128, 256, 512])
    def test_lane_multiples_stay_row_major(self, d):
        x = jnp.zeros((300, d), jnp.float32)
        xk, c0, block, feature_major = _lloyd_operands(x, x[:9], None)
        assert lloyd_form(d) == "row_major" and not feature_major
        assert xk.shape == (304, d) and block == 304 and c0.shape == (128, d)

    def test_block_m_overrides_the_rows_of_either_form(self):
        for d, want in ((64, 256), (128, 200)):
            x = jnp.zeros((1000, d), jnp.float32)
            xk, _, block, fm = _lloyd_operands(x, x[:3], 200)
            assert block == want  # lanes come in tiles of 128
            assert xk.shape[1 if fm else 0] % block == 0

    def test_empty_cluster_keeps_center_feature_major(self):
        # d=64 with a centre that captures nothing, across several blocks
        rng = np.random.default_rng(3)
        x = np.vstack([
            rng.standard_normal((200, 64)).astype(np.float32) * 0.1,
            rng.standard_normal((200, 64)).astype(np.float32) * 0.1 + 2.0,
        ])
        c0 = np.stack([x[0], x[-1], np.full(64, 100.0, np.float32)])
        got_c, got_l, _, _ = lloyd_fit_pallas(
            jnp.asarray(x), jnp.asarray(c0), 400, 5, jnp.float32(0.0),
            block_m=128, interpret=True,
        )
        want_c, want_l, _, _ = _lloyd_fit(
            jnp.asarray(x), jnp.ones((400,), jnp.float32), jnp.asarray(c0),
            5, jnp.float32(0.0),
        )
        np.testing.assert_array_equal(np.asarray(got_c)[2], c0[2])
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))

    @pytest.mark.parametrize("d", [64, 128])
    def test_rows_past_lim_drop_out(self, d):
        # rows at or past n hold anything (here: far-off values inside a
        # block and past it); the centres are those of the first n rows
        n, pad, k = 333, 51, 4
        x, protos = _blobs(n, d, k, seed=11)
        xp = np.vstack([x, np.full((pad, d), 1e3, np.float32)])
        c0 = (protos + 0.25).astype(np.float32)
        got_c, got_l, got_i, _ = lloyd_fit_pallas(
            jnp.asarray(xp), jnp.asarray(c0), n, 4, jnp.float32(-1.0),
            block_m=128, interpret=True,
        )
        want_c, want_l, want_i, _ = _lloyd_fit(
            jnp.asarray(x), jnp.ones((n,), jnp.float32), jnp.asarray(c0),
            4, jnp.float32(-1.0),
        )
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got_l)[:n], np.asarray(want_l))
        np.testing.assert_allclose(float(got_i), float(want_i), rtol=1e-3)

    @pytest.mark.parametrize("d", [64, 128])
    def test_sharded_fit_on_mesh_both_forms(self, d):
        # shard_map + one psum an iteration around either kernel; the last
        # shards hold tail pad, which lim masks
        import heat_tpu as ht

        comm = ht.get_comm()
        n, k = 40 * comm.size + 3, 5
        x, protos = _blobs(n, d, k, seed=d)
        xb = ht.array(x, split=0)._masked(0)
        m = xb.shape[0]
        c0 = (protos + 0.25).astype(np.float32)
        want_c, want_l, want_i, _ = _lloyd_fit(
            jnp.asarray(np.pad(x, ((0, m - n), (0, 0)))),
            jnp.asarray((np.arange(m) < n).astype(np.float32)),
            jnp.asarray(c0), 5, jnp.float32(-1.0),
        )
        got_c, got_l, got_i, got_it = lloyd_fit_pallas_sharded(
            comm, xb, jnp.asarray(c0), n, 5, jnp.float32(-1.0),
            block_m=16, interpret=True,
        )
        assert int(got_it) == 5
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(got_l)[:n], np.asarray(want_l)[:n]
        )
        np.testing.assert_allclose(float(got_i), float(want_i), rtol=1e-3)

    @pytest.mark.parametrize(
        "n,pad,d,k,block,precision",
        [
            (300, 0, 64, 3, 128, "HIGHEST"),  # rows no block multiple
            (1000, 0, 64, 8, 256, "HIGHEST"),
            (500, 0, 64, 130, 128, "HIGHEST"),  # 17 sublane tiles of scores
            (333, 51, 64, 8, 128, "HIGHEST"),  # rows past lim, inside a block and past it
            (257, 7, 18, 9, None, "HIGHEST"),  # the form's own block
            (300, 0, 128, 3, 64, "HIGHEST"),
            (1000, 24, 128, 8, 256, "HIGHEST"),
            (200, 0, 128, 130, None, "HIGHEST"),
            # the split product as the chip runs it: the same rows, the
            # products to ~2^-16 of |x||c| where the XLA pass on a CPU is exact
            (1000, 24, 64, 8, 256, "bf16x3"),
            (1000, 24, 128, 8, 256, "bf16x3"),
        ],
    )
    def test_final_pass_against_the_xla_pass(self, n, pad, d, k, block, precision):
        # no iteration: labels and inertia against the centres given, two of
        # them equal (the first of a tie wins) and one of them a row of X
        x, protos = _blobs(n, d, k, seed=n + d + k)
        xp = np.vstack([x, np.full((pad, d), 1e3, np.float32)])
        c0 = (protos + 0.25).astype(np.float32)
        c0[1] = c0[0]
        c0[2] = x[5]
        w = (np.arange(n + pad) < n).astype(np.float32)
        want_l, want_i = _lloyd_final(jnp.asarray(xp), jnp.asarray(w), jnp.asarray(c0))
        got_c, got_l, got_i, got_it = lloyd_fit_pallas(
            jnp.asarray(xp), jnp.asarray(c0), n, 0, jnp.float32(-1.0),
            block_m=block, interpret=True, precision=precision,
        )
        assert int(got_it) == 0 and got_l.shape == (n + pad,)
        np.testing.assert_array_equal(np.asarray(got_c), c0)
        np.testing.assert_array_equal(np.asarray(got_l)[:n], np.asarray(want_l)[:n])
        assert 1 not in np.asarray(got_l)[:n] and np.asarray(got_l)[5] == 2
        np.testing.assert_allclose(
            float(got_i), float(want_i), rtol=1e-6 if precision == "HIGHEST" else 2e-4)

    @pytest.mark.parametrize("d", [64, 128])
    def test_sharded_final_pass_on_a_mesh_of_four(self, d):
        # the pass inside the shard_map: labels leave split by rows, the
        # inertia by one psum; 4 x 41 buffer rows for 163, so the last
        # shard is ragged
        import jax

        import heat_tpu as ht
        from heat_tpu.core.communication import MeshCommunication

        comm = MeshCommunication(devices=jax.devices()[:4])
        n, k = 163, 5
        x, protos = _blobs(n, d, k, seed=d + 1)
        xb = ht.array(x, split=0, comm=comm)._masked(0)
        m = xb.shape[0]
        assert m == 164
        c0 = (protos + 0.25).astype(np.float32)
        c0[1] = c0[0]
        want_l, want_i = _lloyd_final(
            jnp.asarray(np.pad(x, ((0, m - n), (0, 0)))),
            jnp.asarray((np.arange(m) < n).astype(np.float32)), jnp.asarray(c0),
        )
        _, got_l, got_i, _ = lloyd_fit_pallas_sharded(
            comm, xb, jnp.asarray(c0), n, 0, jnp.float32(-1.0),
            block_m=16, interpret=True, precision="HIGHEST",
        )
        assert got_l.sharding.spec == comm.spec(0, 1)
        np.testing.assert_array_equal(np.asarray(got_l)[:n], np.asarray(want_l)[:n])
        np.testing.assert_allclose(float(got_i), float(want_i), rtol=1e-6)

    @pytest.mark.parametrize(
        "d,counter,assign",
        [
            (64, "kmeans.lloyd.feature_major", "kmeans.assign.kernel"),
            (128, "kmeans.lloyd.row_major", "kmeans.assign.xla"),
        ],
    )
    def test_fit_counts_the_form_it_took(self, monkeypatch, d, counter, assign):
        # KMeans.fit as on a TPU: the gate open, the kernels interpreted
        import heat_tpu as ht
        from heat_tpu import telemetry
        from heat_tpu.cluster import pallas_lloyd

        monkeypatch.setattr(pallas_lloyd, "pallas_lloyd_applicable", lambda *a: True)
        for name in ("lloyd_fit_pallas", "lloyd_fit_pallas_sharded"):
            monkeypatch.setattr(
                pallas_lloyd, name,
                functools.partial(getattr(pallas_lloyd, name), interpret=True),
            )
        x, protos = _blobs(160, d, 3, seed=2)
        counters = telemetry.get_registry().counters
        forms = ("kmeans.lloyd.feature_major", "kmeans.lloyd.row_major")
        assigns = ("kmeans.assign.kernel", "kmeans.assign.xla")
        before = {c: counters.get(c, 0) for c in forms + assigns}
        km = ht.cluster.KMeans(
            n_clusters=3, init=ht.array(protos + 0.25), max_iter=3, tol=-1.0
        ).fit(ht.array(x, split=0))
        assert km.n_iter_ == 3 and km.labels_.dtype == ht.int64
        after = {c: counters.get(c, 0) for c in before}
        for said, among in ((counter, forms), (assign, assigns)):
            assert after[said] == before[said] + 1  # once a fit
            assert sum(after[c] - before[c] for c in among) == 1
        km.predict(ht.array(x, split=0))
        assert {c: counters.get(c, 0) for c in before} == after  # none on predict

    def test_xla_fit_counts_no_form(self):
        # off the TPU the gate is shut: the XLA fit, no form, and its own
        # pass for the labels
        import heat_tpu as ht
        from heat_tpu import telemetry

        x, protos = _blobs(160, 8, 3, seed=4)
        counters = telemetry.get_registry().counters
        names = ("kmeans.lloyd.feature_major", "kmeans.lloyd.row_major",
                 "kmeans.assign.kernel", "kmeans.assign.xla")
        before = [counters.get(c, 0) for c in names]
        ht.cluster.KMeans(n_clusters=3, init=ht.array(protos), max_iter=2).fit(
            ht.array(x, split=0))
        assert [counters.get(c, 0) - b for c, b in zip(names, before)] == [0, 0, 0, 1]
