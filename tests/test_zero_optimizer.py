"""ZeroOptimizer (ISSUE 15): reduce-scatter → shard update → all-gather,
optimizer state sharded 1/p.

Oracles: identical trajectories vs :class:`DataParallelOptimizer` /
:class:`DataParallel` applying the same gradients (bitwise — the update
arithmetic is elementwise, so sharding the state cannot change a single
element); a strictly lower optimizer-state live-bytes watermark than the
replicated base; checkpoint/restore riding resilience with
cross-topology bit-exact restore (the elastic-resume seed); composition
with the tiered collectives and the compressed gradient wire.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import heat_tpu as ht
from heat_tpu.core.communication import MeshCommunication
from heat_tpu.optim import DataParallelOptimizer, ZeroOptimizer
from heat_tpu.parallel import fsdp


@pytest.fixture(scope="module")
def comm():
    return ht.get_comm()


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((13, 3)).astype(np.float32)),
        "b": jnp.zeros((3,), jnp.float32),
    }


def _grads(params, seed=1):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda l: jnp.asarray(
            rng.standard_normal(l.shape).astype(np.float32)
        ),
        params,
    )


def _bits(tree):
    return [np.asarray(l).tobytes() for l in jax.tree.leaves(tree)]


class TestFlatChunk:
    def test_ceil_rule(self):
        assert fsdp.flat_chunk(10, 4) == 3
        assert fsdp.flat_chunk(8, 4) == 2
        assert fsdp.flat_chunk(1, 4) == 1

    def test_blockwise_rounds_to_blocks(self):
        # chunk >= block: whole blocks; chunk < block: untouched
        assert fsdp.flat_chunk(4 * 130, 4, "blockwise", 128) == 256
        assert fsdp.flat_chunk(40, 4, "blockwise", 128) == 10

    def test_shard_unshard_roundtrip(self, comm):
        x = {"a": jnp.arange(23.0), "s": jnp.arange(6.0).reshape(2, 3)}
        sh = fsdp.flat_shard_pytree(x, comm)
        for k in x:
            got = fsdp.flat_unshard_leaf(sh[k], x[k].shape, x[k].dtype)
            assert got.tobytes() == np.asarray(x[k]).tobytes()


class TestTrajectoryParity:
    def test_bitwise_parity_with_replicated_base_sgd(self, comm):
        params = _params()
        grads = _grads(params)
        zo = ZeroOptimizer(optax.sgd(0.1))
        dp = DataParallelOptimizer(optax.sgd(0.1))
        zp, zs = params, zo.init(params)
        pp, ps = params, dp.init(params)
        for _ in range(5):
            zp, zs = zo.step(zp, zs, grads)
            pp, ps = dp.step(pp, ps, grads)
        assert _bits(zp) == _bits(pp)

    @pytest.mark.parametrize("make", [
        lambda: optax.sgd(0.1, momentum=0.9),
        lambda: optax.adam(1e-2),
    ])
    def test_trajectory_parity_with_replicated_base(self, comm, make):
        """Momentum/Adam chains multiply-adds, and XLA CPU's
        shape-dependent FMA contraction can differ by 1 ulp between the
        (chunk,) and full-leaf lowerings of the SAME elementwise math —
        so these pin tight allclose, not bytes (sgd above pins bytes)."""
        params = _params()
        grads = _grads(params)
        zo, dp = ZeroOptimizer(make()), DataParallelOptimizer(make())
        zp, zs = params, zo.init(params)
        pp, ps = params, dp.init(params)
        for _ in range(5):
            zp, zs = zo.step(zp, zs, grads)
            pp, ps = dp.step(pp, ps, grads)
        for a, b in zip(jax.tree.leaves(zp), jax.tree.leaves(pp)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            )

    def test_state_is_actually_sharded(self, comm):
        if comm.size < 2:
            pytest.skip("needs >1 device")
        zo = ZeroOptimizer(optax.adam(1e-2))
        state = zo.init(_params())
        sharded = [
            l for l in jax.tree.leaves(state)
            if getattr(l, "ndim", 0) == 2 and l.shape[0] == comm.size
        ]
        assert sharded, "no state leaf carries the (p, chunk) layout"
        for l in sharded:
            shapes = {s.data.shape for s in l.addressable_shards}
            assert shapes == {(1, l.shape[1])}

    def test_watermark_strictly_below_replicated(self, comm):
        """The acceptance oracle: sharded-state live bytes per device
        strictly below the replicated-state figure."""
        if comm.size < 2:
            pytest.skip("needs >1 device")
        params = _params()
        zo, dp = ZeroOptimizer(optax.adam(1e-2)), DataParallelOptimizer(
            optax.adam(1e-2)
        )
        zb = zo.state_bytes_per_device(zo.init(params))
        db = sum(
            np.asarray(l).nbytes for l in jax.tree.leaves(dp.init(params))
        )
        assert 0 < zb < db


class TestTrainStep:
    def _data(self, comm, seed=2):
        rng = np.random.default_rng(seed)
        xb = rng.standard_normal((8 * comm.size, 16)).astype(np.float32)
        yb = rng.standard_normal((8 * comm.size, 1)).astype(np.float32)
        return (
            jax.device_put(jnp.asarray(xb), comm.sharding(0, 2)),
            jax.device_put(jnp.asarray(yb), comm.sharding(0, 2)),
        )

    @staticmethod
    def _loss(params, x, y):
        return jnp.mean((x @ params["w2"] - y) ** 2)

    def test_bitwise_parity_with_dataparallel_step(self, comm):
        """reduce-scatter-mean + shard update + gather == the DP psum
        step, bit-for-bit (exact wire)."""
        P0 = {"w2": jnp.zeros((16, 1), jnp.float32)}
        bx, by = self._data(comm)
        zo = ZeroOptimizer(optax.sgd(0.05))
        zstep = zo.make_train_step(self._loss)
        zp, zs = P0, zo.init(P0)
        dpw = ht.nn.DataParallel(
            lambda pr, x: x @ pr["w2"], optimizer=optax.sgd(0.05),
            blocking_parameter_updates=True,
        )
        dstep = dpw.make_train_step(self._loss, optax.sgd(0.05))
        dp_p, dp_s = P0, optax.sgd(0.05).init(P0)
        for _ in range(6):
            zp, zs, zloss = zstep(zp, zs, bx, by)
            dp_p, dp_s, dloss = dstep(dp_p, dp_s, bx, by)
        if comm.size & (comm.size - 1) == 0:
            # power-of-two mesh: the mean-of-shard-means divisions are
            # exact powers of two, so the two gradient paths round
            # identically — bitwise
            assert _bits(zp) == _bits(dp_p)
        else:
            # odd mesh: 1/p is inexact, the shard-mean/p and global-mean
            # roundings differ by ulps
            for a, b in zip(jax.tree.leaves(zp), jax.tree.leaves(dp_p)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
                )
        assert float(zloss) == pytest.approx(float(dloss), rel=1e-6)

    @pytest.mark.parametrize("wire", ["off", "bf16", "int8", "blockwise"])
    def test_replicated_outputs_same_bits_on_every_device(self, comm, wire):
        """Both steps run with check_vma=False, so nothing checks their
        P() outputs: the gathered parameters and the loss must be one
        value on every position."""
        P0 = {"w2": jnp.zeros((16, 1), jnp.float32)}
        bx, by = self._data(comm)
        zo = ZeroOptimizer(optax.adam(5e-2), precision=wire)
        step = zo.make_train_step(self._loss)
        p, s = P0, zo.init(P0)
        for _ in range(3):
            p, s, loss = step(p, s, bx, by)
        grads = jax.grad(self._loss)(p, bx, by)
        p2, _ = zo.step(p, s, grads)
        for leaf in jax.tree.leaves((p, loss, p2)):
            shards = [np.asarray(sh.data) for sh in leaf.addressable_shards]
            assert len(shards) == comm.size
            assert all(sh.tobytes() == shards[0].tobytes() for sh in shards)

    def test_loss_decreases(self, comm):
        P0 = {"w2": jnp.zeros((16, 1), jnp.float32)}
        bx, by = self._data(comm)
        zo = ZeroOptimizer(optax.adam(5e-2))
        step = zo.make_train_step(self._loss)
        p, s = P0, zo.init(P0)
        losses = []
        for _ in range(8):
            p, s, loss = step(p, s, bx, by)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.parametrize("wire", ["bf16", "int8", "blockwise"])
    def test_compressed_gradient_wire_tracks_exact(self, comm, wire):
        if comm.size < 2:
            pytest.skip("needs >1 device")
        P0 = {"w2": jnp.zeros((16, 1), jnp.float32)}
        bx, by = self._data(comm)

        def run(precision):
            zo = ZeroOptimizer(optax.sgd(0.05), precision=precision)
            step = zo.make_train_step(self._loss)
            p, s = P0, zo.init(P0)
            for _ in range(6):
                p, s, _ = step(p, s, bx, by)
            return np.asarray(p["w2"])

        exact, got = run("off"), run(wire)
        assert np.abs(got - exact).max() < 5e-2

    def test_composes_with_tiered_collectives(self, comm, monkeypatch):
        if comm.size < 4 or comm.size % 2:
            pytest.skip("needs an even mesh >= 4")
        P0 = {"w2": jnp.zeros((16, 1), jnp.float32)}
        bx, by = self._data(comm)

        def run():
            zo = ZeroOptimizer(optax.sgd(0.05))
            step = zo.make_train_step(self._loss)
            p, s = P0, zo.init(P0)
            for _ in range(4):
                p, s, _ = step(p, s, bx, by)
            return np.asarray(p["w2"])

        monkeypatch.setenv("HEAT_TPU_HIERARCHICAL", "0")
        flat = run()
        monkeypatch.setenv("HEAT_TPU_HIERARCHICAL", "1")
        hier = run()
        # the tiered reduce-scatter reassociates the gradient sum —
        # values agree to fp tolerance, and exactly under exact sums
        np.testing.assert_allclose(hier, flat, rtol=1e-5, atol=1e-6)


class TestCheckpoint:
    def test_roundtrip_same_topology_bitwise(self, comm, tmp_path):
        params = _params()
        zo = ZeroOptimizer(optax.adam(1e-2))
        p, s = params, zo.init(params)
        for _ in range(3):
            p, s = zo.step(p, s, _grads(params))
        zo.save_checkpoint(str(tmp_path / "ck"), p, s)
        p2, s2 = zo.load_checkpoint(str(tmp_path / "ck"), params)
        assert _bits(p2) == _bits(p)
        # one more identical step from both: bitwise-identical params
        g = _grads(params, seed=9)
        a, _ = zo.step(p, s, g)
        b, _ = zo.step(p2, s2, g)
        assert _bits(a) == _bits(b)

    def test_cross_topology_restore_bit_exact(self, tmp_path):
        """The elastic-resume seed: checkpoint on one mesh size, restore
        on another, continue bit-exactly (replicated-grads step — the
        update arithmetic is elementwise, so shard boundaries cannot
        change any element)."""
        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs >= 4 devices")
        comm_a = MeshCommunication(devices=devs[:4])
        comm_b = MeshCommunication(devices=devs[:2])
        params = _params()
        za = ZeroOptimizer(optax.adam(1e-2), comm=comm_a)
        p, s = params, za.init(params)
        for _ in range(3):
            p, s = za.step(p, s, _grads(params))
        za.save_checkpoint(str(tmp_path / "ck"), p, s)

        zb = ZeroOptimizer(optax.adam(1e-2), comm=comm_b)
        pb, sb = zb.load_checkpoint(str(tmp_path / "ck"), params)
        # the RESTORE is bit-exact: same logical params and state bytes
        assert _bits(pb) == _bits(p)
        for la, lb in zip(
            jax.tree.leaves(za._logical_state(p, s)),
            jax.tree.leaves(zb._logical_state(pb, sb)),
        ):
            assert np.asarray(la).tobytes() == np.asarray(lb).tobytes()
        # and the continued trajectory agrees (allclose, not bytes: the
        # two meshes lower different chunk shapes, and XLA CPU's FMA
        # contraction is shape-dependent — see TestTrajectoryParity)
        g = _grads(params, seed=11)
        a, _ = za.step(p, s, g)
        b, _ = zb.step(pb, sb, g)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-6, atol=1e-7
            )

    def test_rejects_foreign_checkpoint(self, comm, tmp_path):
        from heat_tpu import resilience

        params = _params()
        zo = ZeroOptimizer(optax.sgd(0.1))
        resilience.save_checkpoint(
            {"params": params,
             "opt_state": zo._logical_state(params, zo.init(params))},
            str(tmp_path / "ck"), extra={"algo": "daso"},
        )
        with pytest.raises(resilience.CheckpointError, match="not zero"):
            zo.load_checkpoint(str(tmp_path / "ck"), params)


class TestBlockwiseLayout:
    def test_blockwise_wire_aligns_chunks(self, comm):
        """The blockwise reduce-scatter's padded chunk boundaries must
        coincide with the state shards (flat_chunk's fixed point)."""
        if comm.size < 2:
            pytest.skip("needs >1 device")
        P0 = {"w2": jnp.zeros((130 * comm.size, 1), jnp.float32)}
        zo = ZeroOptimizer(optax.sgd(0.05), precision="blockwise")
        rng = np.random.default_rng(4)
        bx = jax.device_put(
            jnp.asarray(rng.standard_normal(
                (4 * comm.size, 130 * comm.size)
            ).astype(np.float32)),
            comm.sharding(0, 2),
        )
        by = jax.device_put(
            jnp.zeros((4 * comm.size, 1), jnp.float32), comm.sharding(0, 2)
        )

        def loss(params, x, y):
            return jnp.mean((x @ params["w2"] - y) ** 2)

        step = zo.make_train_step(loss)
        p, s = P0, zo.init(P0)
        p, s, l0 = step(p, s, bx, by)
        p, s, l1 = step(p, s, bx, by)
        assert np.isfinite(float(l0)) and np.isfinite(float(l1))
