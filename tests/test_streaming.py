"""heat_tpu.streaming — online estimators, out-of-core ingestion, and
versioned fit-while-serve (ISSUE 16).

Covers: the chunked-read error surface of core/io (truncated final
chunk, empty range, negative rows, non-pair), ChunkStream iteration
(multi-file concatenation equality, per-file chunk counting, skip_rows
resume, budget-driven auto-sizing), the partial_fit-over-K-chunks vs
one-shot equivalence battery (StreamingMoments single-chunk bit-exact
vs the kernel, K-chunk and merge to documented tolerance;
MiniBatchKMeans vs batch KMeans on separable data; Lasso epochs vs the
one-shot coordinate fit), checkpoint/resume bit-exactness (same chunk
sequence → identical carry) plus the cross-mesh restore, the
zero-compile steady-stream oracle (``site_stats("streaming.")`` and a
CompileWatcher window), the versioned-register regression (duplicate
names raise; ``replace=True`` is an explicit publish that bumps), the
wire version round-trip, the live==offline ``streaming`` telemetry
block, and — subprocess-verified, slow-marked — the rolling replica
update: a 2-replica pool rolls onto a v2 checkpoint under live traffic
with zero failed requests, every survivor reporting the new version,
and SIGKILL-mid-roll recovery.
"""

import os
import threading

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import serve, streaming, telemetry
from heat_tpu.core import io as hio
from heat_tpu.core import program_cache
from heat_tpu.core.statistics import chunk_moments
from heat_tpu.regression import Lasso
from heat_tpu.serve.net import wire


@pytest.fixture()
def rng():
    return np.random.default_rng(16)


def _npy(tmp_path, name, arr):
    p = str(tmp_path / name)
    np.save(p, arr)
    return p


def _h5(tmp_path, name, arr, dataset="data"):
    import h5py

    p = str(tmp_path / name)
    with h5py.File(p, "w") as f:
        f.create_dataset(dataset, data=arr)
    return p


# -- core/io chunked reads ----------------------------------------------------


class TestIOChunks:
    def test_npy_row_range_matches_slice(self, rng, tmp_path):
        a = rng.standard_normal((37, 4)).astype(np.float32)
        p = _npy(tmp_path, "a.npy", a)
        got = hio.load_npy(p, chunks=(5, 12), split=0)
        assert np.array_equal(np.asarray(got.numpy()), a[5:12])
        assert got.shape == (7, 4)

    @pytest.mark.skipif(not ht.io.supports_hdf5(), reason="h5py missing")
    def test_hdf5_row_range_matches_slice(self, rng, tmp_path):
        a = rng.standard_normal((29, 3)).astype(np.float32)
        p = _h5(tmp_path, "a.h5", a)
        got = hio.load_hdf5(p, "data", chunks=(10, 29), split=0)
        assert np.array_equal(np.asarray(got.numpy()), a[10:29])

    def test_truncated_final_chunk_is_a_clear_error(self, rng, tmp_path):
        p = _npy(tmp_path, "a.npy", rng.standard_normal((10, 2)))
        with pytest.raises(ValueError, match="truncated final chunk"):
            hio.load_npy(p, chunks=(8, 11))

    def test_empty_row_range_is_a_clear_error(self, rng, tmp_path):
        p = _npy(tmp_path, "a.npy", rng.standard_normal((10, 2)))
        with pytest.raises(ValueError, match="empty row range"):
            hio.load_npy(p, chunks=(5, 5))
        with pytest.raises(ValueError, match="empty row range"):
            hio.load_npy(p, chunks=(7, 3))

    def test_negative_and_malformed_chunks(self, rng, tmp_path):
        p = _npy(tmp_path, "a.npy", rng.standard_normal((10, 2)))
        with pytest.raises(ValueError, match="negative"):
            hio.load_npy(p, chunks=(-1, 4))
        with pytest.raises(TypeError, match="pair"):
            hio.load_npy(p, chunks="0:4")
        with pytest.raises(TypeError, match="pair"):
            hio.load_npy(p, chunks=(1, 2, 3))

    def test_dataset_shape_header_peek(self, rng, tmp_path):
        a = rng.standard_normal((11, 5)).astype(np.float64)
        p = _npy(tmp_path, "a.npy", a)
        assert hio.dataset_shape(p) == (11, 5)

    @pytest.mark.skipif(not ht.io.supports_hdf5(), reason="h5py missing")
    def test_dataset_shape_hdf5(self, rng, tmp_path):
        p = _h5(tmp_path, "a.h5", rng.standard_normal((7, 2)))
        assert hio.dataset_shape(p, "data") == (7, 2)


# -- ChunkStream --------------------------------------------------------------


class TestChunkStream:
    def test_multi_file_concatenation_equality(self, rng, tmp_path):
        a = rng.standard_normal((37, 4)).astype(np.float32)
        b = rng.standard_normal((23, 4)).astype(np.float32)
        cs = streaming.ChunkStream(
            [_npy(tmp_path, "a.npy", a), _npy(tmp_path, "b.npy", b)],
            chunk_rows=16,
        )
        assert cs.nrows() == 60
        chunks = list(cs)
        # chunking restarts at each file boundary: 3 + 2 blocks
        assert len(chunks) == len(cs) == 5
        got = np.concatenate([np.asarray(c.numpy()) for c in chunks])
        assert np.array_equal(got, np.concatenate([a, b]))
        assert cs.rows_read == 60 and cs.chunks_read == 5

    def test_skip_rows_resumes_across_file_boundary(self, rng, tmp_path):
        a = rng.standard_normal((20, 3)).astype(np.float32)
        b = rng.standard_normal((12, 3)).astype(np.float32)
        paths = [_npy(tmp_path, "a.npy", a), _npy(tmp_path, "b.npy", b)]
        cs = streaming.ChunkStream(paths, chunk_rows=8, skip_rows=24)
        got = np.concatenate([np.asarray(c.numpy()) for c in cs])
        assert np.array_equal(got, np.concatenate([a, b])[24:])

    def test_budget_auto_sizing_bounds_chunk_bytes(
        self, rng, tmp_path, monkeypatch
    ):
        # 64Ki rows x 8 f32 = 2 MiB — twice the floored temp budget
        a = np.zeros((1 << 16, 8), np.float32)
        p = _npy(tmp_path, "a.npy", a)
        monkeypatch.setenv("HEAT_TPU_HBM_BUDGET", "4M")  # temp budget = 1 MiB
        cs = streaming.ChunkStream(p)
        assert cs.chunk_bytes() <= 1 << 20
        assert cs.chunk_bytes() < cs.load_all_bytes()
        monkeypatch.delenv("HEAT_TPU_HBM_BUDGET")
        big = streaming.ChunkStream(p)
        assert big.chunk_rows == 1 << 16  # default budget swallows the file

    def test_explicit_knob_overrides_auto(self, rng, tmp_path, monkeypatch):
        p = _npy(tmp_path, "a.npy", rng.standard_normal((100, 2)))
        monkeypatch.setenv("HEAT_TPU_STREAM_CHUNK_ROWS", "7")
        assert streaming.ChunkStream(p).chunk_rows == 7

    def test_mismatched_feature_shape_raises(self, rng, tmp_path):
        p1 = _npy(tmp_path, "a.npy", rng.standard_normal((5, 3)))
        p2 = _npy(tmp_path, "b.npy", rng.standard_normal((5, 4)))
        with pytest.raises(ValueError, match="row shape"):
            streaming.ChunkStream([p1, p2])

    def test_bad_skip_rows_raises(self, rng, tmp_path):
        p = _npy(tmp_path, "a.npy", rng.standard_normal((5, 3)))
        with pytest.raises(ValueError, match="skip_rows"):
            streaming.ChunkStream(p, skip_rows=6)


# -- equivalence battery ------------------------------------------------------


class TestStreamingMoments:
    def test_single_chunk_bit_exact_vs_kernel(self, rng):
        a = rng.standard_normal((32, 6)).astype(np.float32)
        x = ht.array(a, split=0)
        n, mu, m2 = chunk_moments(x)
        sm = streaming.StreamingMoments()
        sm.partial_fit(x)
        # chan-merge into an empty carry is the identity: bit-exact
        assert np.array_equal(sm.mean, np.asarray(mu, dtype=np.float64))
        assert np.array_equal(
            sm.var(), np.asarray(m2, dtype=np.float64) / float(n)
        )

    def test_k_chunks_match_full_pass_tolerance(self, rng):
        a = rng.standard_normal((96, 5)).astype(np.float32)
        sm = streaming.StreamingMoments()
        for lo in range(0, 96, 25):  # ragged final chunk on purpose
            sm.partial_fit(ht.array(a[lo:lo + 25], split=0))
        # documented tolerance: the merge tree reassociates the f32 sums
        assert np.allclose(sm.mean, a.mean(axis=0), atol=1e-5)
        assert np.allclose(sm.var(), a.var(axis=0), rtol=1e-5, atol=1e-5)
        assert np.allclose(
            sm.var(ddof=1), a.var(axis=0, ddof=1), rtol=1e-5, atol=1e-5
        )

    def test_merge_two_streams(self, rng):
        a = rng.standard_normal((40, 3)).astype(np.float32)
        left, right = streaming.StreamingMoments(), streaming.StreamingMoments()
        left.partial_fit(ht.array(a[:24], split=0))
        right.partial_fit(ht.array(a[24:], split=0))
        left.merge(right)
        assert np.allclose(left.mean, a.mean(axis=0), atol=1e-5)
        assert np.allclose(left.var(), a.var(axis=0), rtol=1e-5, atol=1e-5)

    def test_feature_mismatch_raises(self, rng):
        sm = streaming.StreamingMoments()
        sm.partial_fit(ht.array(rng.standard_normal((8, 3)), split=0))
        with pytest.raises(ValueError):
            sm.partial_fit(ht.array(rng.standard_normal((8, 4)), split=0))

    def test_var_before_enough_rows_raises(self, rng):
        sm = streaming.StreamingMoments()
        with pytest.raises(RuntimeError, match="at least one chunk"):
            sm.var()
        sm.partial_fit(ht.array(rng.standard_normal((1, 2)), split=0))
        with pytest.raises(ValueError):
            sm.var(ddof=1)

    def test_checkpoint_resume_bit_exact(self, rng, tmp_path):
        a = rng.standard_normal((60, 4)).astype(np.float32)
        full = streaming.StreamingMoments()
        for lo in range(0, 60, 20):
            full.partial_fit(ht.array(a[lo:lo + 20], split=0))

        half = streaming.StreamingMoments()
        half.partial_fit(ht.array(a[:20], split=0))
        ck = str(tmp_path / "sm.ckpt")
        half.save(ck)
        resumed = streaming.StreamingMoments.restore(ck)
        for lo in range(20, 60, 20):
            resumed.partial_fit(ht.array(a[lo:lo + 20], split=0))
        # same chunk sequence → bit-identical host carry
        assert np.array_equal(full.mean, resumed.mean)
        assert np.array_equal(full.var(), resumed.var())

    def test_cross_mesh_restore_tolerance(self, rng, tmp_path):
        """The carry is mesh-independent host state: a checkpoint taken
        from a split=0 stream restores into a replicated (split=None)
        stream; the two placements only differ by collective-reduction
        order, so the totals agree to tolerance."""
        a = rng.standard_normal((40, 3)).astype(np.float32)
        sm0 = streaming.StreamingMoments()
        sm0.partial_fit(ht.array(a[:20], split=0))
        ck = str(tmp_path / "sm.ckpt")
        sm0.save(ck)
        resumed = streaming.StreamingMoments.restore(ck)
        resumed.partial_fit(ht.array(a[20:], split=None))
        assert np.allclose(resumed.mean, a.mean(axis=0), atol=1e-5)
        assert np.allclose(resumed.var(), a.var(axis=0), rtol=1e-5, atol=1e-5)


class TestMiniBatchKMeans:
    def _blobs(self, rng):
        centers = np.array([[0.0, 0.0], [10.0, 10.0], [-10.0, 8.0]],
                           np.float32)
        pts = np.concatenate([
            rng.normal(c, 0.5, size=(60, 2)).astype(np.float32)
            for c in centers
        ])
        rng.shuffle(pts)
        return pts

    def test_chunks_match_one_shot_on_separable_data(self, rng):
        from heat_tpu.cluster import KMeans

        pts = self._blobs(rng)
        # one start per blob for both estimators: a random draw can seed
        # two centers in one blob, and then Lloyd's local optimum — not
        # chunking — is what the comparison measures
        init = ht.array(
            np.array([[1.0, 1.0], [9.0, 9.0], [-9.0, 7.0]], np.float32)
        )
        mb = streaming.MiniBatchKMeans(n_clusters=3, init=init, inner_iter=5)
        for lo in range(0, 180, 45):
            mb.partial_fit(ht.array(pts[lo:lo + 45], split=0))
        km = KMeans(n_clusters=3, init=init, max_iter=50)
        km.fit(ht.array(pts, split=0))
        got = np.sort(np.asarray(mb.cluster_centers_.numpy()), axis=0)
        ref = np.sort(np.asarray(km.cluster_centers_.numpy()), axis=0)
        # documented tolerance: order-dependent updates, separable data
        assert np.allclose(got, ref, atol=1e-3)

    def test_checkpoint_resume_bit_exact(self, rng, tmp_path):
        pts = self._blobs(rng)
        straight = streaming.MiniBatchKMeans(n_clusters=3, random_state=0)
        straight.partial_fit(ht.array(pts[:45], split=0))
        straight.partial_fit(ht.array(pts[45:90], split=0))
        ck = str(tmp_path / "mb.ckpt")
        straight.save(ck)
        straight.partial_fit(ht.array(pts[90:135], split=0))
        resumed = streaming.MiniBatchKMeans.restore(ck)
        resumed.partial_fit(ht.array(pts[90:135], split=0))
        assert np.array_equal(straight._centers_np, resumed._centers_np)
        assert np.array_equal(straight._counts_np, resumed._counts_np)
        assert resumed.rows_seen == 135 and resumed.chunks_seen == 3

    def test_decay_validation_and_feature_mismatch(self, rng):
        with pytest.raises(ValueError, match="decay"):
            streaming.MiniBatchKMeans(decay=0.0)
        with pytest.raises(ValueError, match="decay"):
            streaming.MiniBatchKMeans(decay=1.5)
        mb = streaming.MiniBatchKMeans(n_clusters=2, random_state=0)
        mb.partial_fit(ht.array(rng.standard_normal((10, 3)), split=0))
        with pytest.raises(ValueError, match="feature columns"):
            mb.partial_fit(ht.array(rng.standard_normal((10, 4)), split=0))

    def test_wrong_checkpoint_kind_refused(self, rng, tmp_path):
        from heat_tpu import resilience

        sm = streaming.StreamingMoments()
        sm.partial_fit(ht.array(rng.standard_normal((8, 2)), split=0))
        ck = str(tmp_path / "sm.ckpt")
        sm.save(ck)
        with pytest.raises(resilience.CheckpointError):
            streaming.MiniBatchKMeans.restore(ck)


class TestLassoPartialFit:
    def test_epochs_approach_one_shot_fit(self, rng):
        a = rng.standard_normal((120, 6)).astype(np.float32)
        w = np.array([2.0, 0.0, -1.5, 0.0, 3.0, 0.0], np.float32)
        y = a @ w + 0.01 * rng.standard_normal(120).astype(np.float32)
        one = Lasso(lam=0.05, max_iter=200)
        one.fit(ht.array(a, split=0), ht.array(y, split=0))
        inc = Lasso(lam=0.05, max_iter=30)
        for _ in range(3):
            for lo in range(0, 120, 40):
                inc.partial_fit(
                    ht.array(a[lo:lo + 40], split=0),
                    ht.array(y[lo:lo + 40], split=0),
                )
        ref = np.asarray(one.coef_.numpy()).ravel()
        got = np.asarray(inc.coef_.numpy()).ravel()
        # documented tolerance: per-chunk coordinate sweeps vs the
        # full-data fit (same support, coefficients within 0.1)
        assert np.allclose(got, ref, atol=0.1)
        assert np.array_equal(np.abs(got) > 1e-6, np.abs(ref) > 1e-6)

    def test_first_partial_fit_equals_fit_on_same_chunk(self, rng):
        """A cold partial_fit starts from zeros — exactly the batch
        fit's initial state — so one chunk gives the same solve."""
        a = rng.standard_normal((40, 4)).astype(np.float32)
        y = (a @ np.arange(4, dtype=np.float32)).astype(np.float32)
        one = Lasso(lam=0.02, max_iter=60)
        one.fit(ht.array(a, split=0), ht.array(y, split=0))
        inc = Lasso(lam=0.02, max_iter=60)
        inc.partial_fit(ht.array(a, split=0), ht.array(y, split=0))
        assert np.allclose(
            np.asarray(one.theta.numpy()), np.asarray(inc.theta.numpy()),
            atol=1e-6,
        )

    def test_feature_mismatch_raises(self, rng):
        inc = Lasso(lam=0.05, max_iter=10)
        a = rng.standard_normal((20, 3)).astype(np.float32)
        y = a.sum(axis=1)
        inc.partial_fit(ht.array(a, split=0), ht.array(y, split=0))
        b = rng.standard_normal((20, 5)).astype(np.float32)
        with pytest.raises(ValueError):
            inc.partial_fit(ht.array(b, split=0), ht.array(y, split=0))


# -- zero-compile steady stream -----------------------------------------------


class TestZeroCompileOracle:
    def test_site_stats_show_one_miss_then_hits(self, rng):
        a = rng.standard_normal((64, 4)).astype(np.float32)
        before = program_cache.site_stats("streaming.moments")
        sm = streaming.StreamingMoments()
        for lo in range(0, 64, 16):
            sm.partial_fit(ht.array(a[lo:lo + 16], split=0))
        after = program_cache.site_stats("streaming.moments")
        assert after["misses"] - before["misses"] <= 1
        assert after["hits"] - before["hits"] >= 3

    def test_steady_stream_has_zero_backend_compiles(self, rng):
        a = rng.standard_normal((80, 4)).astype(np.float32)
        sm = streaming.StreamingMoments()
        mb = streaming.MiniBatchKMeans(n_clusters=2, random_state=0)
        # chunk 0 compiles the programs; the steady tail must not
        chunks = [ht.array(a[lo:lo + 16], split=0) for lo in range(0, 80, 16)]
        sm.partial_fit(chunks[0])
        mb.partial_fit(chunks[0])
        with telemetry.CompileWatcher() as cw:
            for x in chunks[1:]:
                sm.partial_fit(x)
                mb.partial_fit(x)
        assert cw.backend_compiles == 0, (
            f"steady stream compiled {cw.backend_compiles}x"
        )

    def test_short_final_chunk_reuses_minibatch_program(self, rng):
        """The logical row count is an argument (validity weights), not
        a key component: a ragged tail padded to the steady physical
        shape re-enters the warm program."""
        a = rng.standard_normal((40, 3)).astype(np.float32)
        mb = streaming.MiniBatchKMeans(n_clusters=2, random_state=0)
        x0 = ht.array(a[:16], split=0)
        mb.partial_fit(x0)
        before = program_cache.site_stats("streaming.minibatch_kmeans")
        # 10 logical rows, padded up to x0's physical chunk shape
        tail = ht.array(a[16:26], split=0)
        if tuple(tail._masked(0).shape) == tuple(x0._masked(0).shape):
            mb.partial_fit(tail)
            after = program_cache.site_stats("streaming.minibatch_kmeans")
            assert after["misses"] == before["misses"]


# -- versioned registration / publish -----------------------------------------


def _lasso_endpoint(rng):
    a = rng.standard_normal((30, 5)).astype(np.float32)
    y = a @ np.arange(5, dtype=np.float32)
    est = Lasso(lam=0.01, max_iter=50)
    est.fit(ht.array(a, split=0), ht.array(y, split=0))
    return serve.lasso_predict(est)


class TestVersionedRegister:
    def test_duplicate_register_raises_without_replace(self, rng):
        srv = serve.Server()
        try:
            ep = _lasso_endpoint(rng)
            srv.register("pred", ep)
            with pytest.raises(ValueError, match="replace=True"):
                srv.register("pred", ep)
        finally:
            srv.close()

    def test_replace_bumps_version_and_stats_report_it(self, rng):
        srv = serve.Server()
        try:
            srv.register("pred", _lasso_endpoint(rng))
            assert srv.endpoint_version("pred") == 1
            srv.register("pred", _lasso_endpoint(rng), replace=True)
            assert srv.endpoint_version("pred") == 2
            assert srv.stats()["versions"] == {"pred": 2}
        finally:
            srv.close()

    def test_with_params_same_aval_bumps_and_mismatch_raises(self, rng):
        ep = _lasso_endpoint(rng)
        ep2 = ep.with_params([np.asarray(p) * 2 for p in ep.params])
        assert ep2.version == ep.version + 1
        assert ep2.describe()["version"] == ep2.version
        with pytest.raises(ValueError, match="aval"):
            ep.with_params([np.zeros((3, 1), np.float32)])

    def test_publish_swaps_params_and_counts_compiles(self, rng):
        srv = serve.Server(max_batch=4, max_wait_ms=1.0)
        try:
            ep = _lasso_endpoint(rng)
            srv.register("pred", ep)
            srv.warmup()
            q = rng.standard_normal((2, 5)).astype(np.float32)
            v1 = np.asarray(srv.predict("pred", q))
            info = srv.publish(
                "pred", ep.with_params([np.asarray(p) * 2 for p in ep.params])
            )
            assert info["version"] == 2
            # same-aval publish re-enters warm programs: zero compiles
            assert info["backend_compiles"] == 0, info
            v2 = np.asarray(srv.predict("pred", q))
            assert not np.array_equal(v1, v2)  # new params actually serve
        finally:
            srv.close()

    def test_version_survives_save_restore(self, rng, tmp_path):
        srv = serve.Server()
        ck = str(tmp_path / "s.ckpt")
        try:
            ep = _lasso_endpoint(rng)
            srv.register("pred", ep)
            srv.publish("pred", ep.with_params(list(ep.params)), warm=False)
            srv.save(ck)
        finally:
            srv.close()
        srv2 = serve.Server.restore(ck)
        try:
            assert srv2.endpoint_version("pred") == 2
        finally:
            srv2.close()

    def test_wire_version_round_trip(self, rng):
        body = wire.encode_response(
            rng.standard_normal((2, 2)).astype(np.float32), version=7
        )
        assert wire.decode_response_version(body) == 7
        body0 = wire.encode_response(
            rng.standard_normal((2, 2)).astype(np.float32)
        )
        assert wire.decode_response_version(body0) is None


# -- telemetry reconciliation -------------------------------------------------


class TestStreamingTelemetry:
    @pytest.fixture()
    def telem(self):
        reg = telemetry.enable()
        reg.clear()
        yield reg
        telemetry.disable()
        reg.clear()

    def test_summarize_streaming_block_live_equals_offline(
        self, rng, tmp_path, telem
    ):
        a = rng.standard_normal((40, 4)).astype(np.float32)
        p = _npy(tmp_path, "a.npy", a)
        sm = streaming.StreamingMoments()
        for ch in streaming.ChunkStream(p, chunk_rows=16):
            sm.partial_fit(ch)
        ck = str(tmp_path / "sm.ckpt")
        sm.save(ck)
        streaming.StreamingMoments.restore(ck)

        live = telemetry.report.summarize()["streaming"]
        off = telemetry.report.summarize(
            list(telem.events), dict(telem.watermarks)
        )["streaming"]
        assert live == off
        assert live["chunks"] == 3 and live["rows"] == 40
        assert live["checkpoints"] == 1 and live["resumes"] == 1
        assert live["chunk_bytes"] == 16 * 4 * 4
        assert live["rows_per_s"] > 0

    def test_no_streaming_block_without_traffic(self):
        assert "streaming" not in telemetry.report.summarize(events=[])


# -- rolling replica updates (subprocess-verified acceptance path) ------------


def _wait_until(fn, timeout=20.0, what="condition"):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.slow
class TestRollingUpdateSubprocess:
    def test_roll_to_v2_under_traffic_then_chaos(self, rng, tmp_path):
        """2-replica pool rolls onto a v2 checkpoint while a client
        hammers the router: zero failed requests, capacity never below
        two, every survivor reports version 2, answers flip to the new
        parameters, and a SIGKILL after the roll only costs the victim
        (the next spawn is already v2 — the mid-roll crash-recovery
        story)."""
        from heat_tpu.serve.net import ReplicaPool, Router

        y1 = rng.standard_normal((32, 8)).astype(np.float32)
        y2 = (y1 * 2.0).astype(np.float32)
        q = rng.standard_normal((2, 8)).astype(np.float32)

        ck1, ck2 = str(tmp_path / "v1.ckpt"), str(tmp_path / "v2.ckpt")
        srv = serve.Server(max_batch=4, max_wait_ms=1.0)
        ep1 = serve.cdist_query(y1)
        srv.register("cdist", ep1)
        srv.save(ck1)
        srv.publish("cdist", ep1.with_params([y2]), warm=False)
        srv.save(ck2)
        srv.close()

        # in-process references for both versions
        ref1 = serve.Server.restore(ck1)
        want_v1 = np.asarray(ref1.predict("cdist", q))
        ref1.close()
        ref2 = serve.Server.restore(ck2)
        want_v2 = np.asarray(ref2.predict("cdist", q))
        ref2.close()
        assert not np.array_equal(want_v1, want_v2)

        env = {
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
            "HEAT_TPU_TELEMETRY": "1",
            "HEAT_TPU_SERVE_MAX_BATCH": "4",
        }
        pool = ReplicaPool(ck1, 2, mesh=4, env=env,
                           log_dir=str(tmp_path / "logs"))
        failures, answers = [], []
        stop = threading.Event()
        try:
            pool.start()
            # retry_in_flight: queries are idempotent, and a drained
            # replica may reset connections it had accepted — the
            # zero-failed-request roll needs at-least-once re-dispatch
            router = Router(pool, retries=3, poll_ms=50.0, workers=4,
                            retry_in_flight=True)
            try:
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want_v1.tobytes()

                def hammer():
                    while not stop.is_set():
                        try:
                            r = np.asarray(
                                router.predict("cdist", q, timeout=60)
                            )
                            answers.append(r.tobytes())
                        except Exception as e:  # noqa: BLE001
                            failures.append(repr(e))

                t = threading.Thread(target=hammer, daemon=True)
                t.start()
                info = streaming.rolling_update(
                    pool, router, ck2, drain_timeout=60.0
                )
                stop.set()
                t.join(timeout=30)

                assert info["replicas"] == 2
                assert [s["drain_rc"] for s in info["steps"]] == [0, 0]
                assert not failures, failures[:3]
                # every surviving replica reports version 2
                for vmap in info["versions"].values():
                    assert vmap.get("cdist") == 2, info["versions"]
                # traffic flipped from v1 answers to v2 answers, with
                # nothing that matches neither version
                assert answers, "hammer thread produced no traffic"
                assert set(answers) <= {want_v1.tobytes(), want_v2.tobytes()}
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want_v2.tobytes()

                # chaos: SIGKILL one survivor; the sibling answers, and
                # the recovery spawn is already v2 (set_checkpoint)
                live = [h.index for h in pool.replicas
                        if h.state == "up" and h.alive()]
                pool.kill(live[0])
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want_v2.tobytes()
                repl = pool.spawn()
                router.add_target(repl.url)
                _wait_until(
                    lambda: router.stats()["replicas"]
                    .get(repl.url, {}).get("up"),
                    what="recovery replica joining rotation",
                )
                assert pool.stats(repl.index)["versions"] == {"cdist": 2}
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want_v2.tobytes()
            finally:
                stop.set()
                router.close()
        finally:
            pool.close()
