"""Tests for heat_tpu.nn / heat_tpu.optim.

Oracles (SURVEY §4 style): a single-device training run with identical
seeds/data must match DataParallel bit-for-near (grad mean == psum of
sharded batch); DASO in warmup (blocking full sync) must track standard DP;
plateau detector semantics are tested directly against the reference's
documented behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import heat_tpu as ht
from heat_tpu.nn import DataParallel, DataParallelMultiGPU
from heat_tpu.optim import DASO, DataParallelOptimizer, DetectMetricPlateau
from heat_tpu.optim import lr_scheduler


@pytest.fixture(scope="module")
def comm():
    return ht.get_comm()


def make_data(n=None, d=8, seed=0):
    # sizes scale with the mesh so the suite passes at any device count
    p = ht.get_comm().size
    n = 8 * p if n is None else n
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w_true = rng.standard_normal((d, 1)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.standard_normal((n, 1)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def mlp_init(d, h=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.standard_normal((d, h)).astype(np.float32) * 0.1),
        "b1": jnp.zeros((h,), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((h, 1)).astype(np.float32) * 0.1),
        "b2": jnp.zeros((1,), jnp.float32),
    }


def mlp_apply(params, x):
    z = jnp.tanh(x @ params["w1"] + params["b1"])
    return z @ params["w2"] + params["b2"]


def mse_loss(params, x, y):
    return jnp.mean((mlp_apply(params, x) - y) ** 2)


class TestDataParallel:
    def test_matches_single_device_training(self, comm):
        x, y = make_data()
        params0 = mlp_init(8)
        opt = optax.sgd(0.1)

        # single-device oracle
        p_ref = params0
        s_ref = opt.init(p_ref)
        for _ in range(5):
            g = jax.grad(mse_loss)(p_ref, x, y)
            u, s_ref = opt.update(g, s_ref, p_ref)
            p_ref = optax.apply_updates(p_ref, u)

        dp = DataParallel(
            mlp_apply, comm=comm, optimizer=opt, blocking_parameter_updates=True
        )
        step = dp.make_train_step(mse_loss)
        p = jax.device_put(params0, comm.replicated())
        s = opt.init(p)
        xb, yb = dp.shard_batch(x, y)
        for _ in range(5):
            p, s, loss = step(p, s, xb, yb)
        for k in p_ref:
            np.testing.assert_allclose(
                np.asarray(p[k]), np.asarray(p_ref[k]), rtol=1e-5, atol=1e-6
            )

    def test_forward_sharded(self, comm):
        x, _ = make_data()
        dp = DataParallel(mlp_apply, comm=comm)
        params = mlp_init(8)
        out = dp(params, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(mlp_apply(params, x)), rtol=1e-5, atol=1e-6
        )

    def test_rejects_bad_module(self):
        with pytest.raises(TypeError):
            DataParallel(42)

    def test_rejects_padded_dndarray_batch(self, comm):
        n = comm.size + 1  # not divisible -> tail pad
        a = ht.random.randn(n, 4, split=0, comm=comm)
        dp = DataParallel(mlp_apply, comm=comm)
        if a.pad_count:
            with pytest.raises(ValueError, match="divide evenly"):
                dp.shard_batch(a)

    def test_loss_decreases(self, comm):
        x, y = make_data(n=16 * comm.size)
        dp = DataParallel(
            mlp_apply, comm=comm, optimizer=optax.adam(1e-2),
            blocking_parameter_updates=True,
        )
        step = dp.make_train_step(mse_loss)
        p = jax.device_put(mlp_init(8, seed=1), comm.replicated())
        s = dp.optimizer.init(p)
        xb, yb = dp.shard_batch(x, y)
        first = last = None
        for i in range(30):
            p, s, loss = step(p, s, xb, yb)
            if i == 0:
                first = float(loss)
            last = float(loss)
        assert last < first


class TestDataParallelNonBlocking:
    """Double-buffered (overlapped) DP — reference data_parallel.py:243-297:
    global grads are applied just-in-time one iteration later; iteration 0
    applies zeros (:276)."""

    def test_first_step_applies_zeros(self, comm):
        x, y = make_data()
        dp = DataParallel(mlp_apply, comm=comm, optimizer=optax.sgd(0.1))
        assert dp.blocking_parameter_updates is False  # reference default
        step = dp.make_train_step(mse_loss)
        p0 = jax.device_put(mlp_init(8), comm.replicated())
        before = {k: np.asarray(v) for k, v in p0.items()}  # the step donates p0
        s = dp.optimizer.init(p0)
        xb, yb = dp.shard_batch(x, y)
        p1, s, pending, loss = step(p0, s, dp.init_pending(p0), xb, yb)
        for k in before:  # zero grads applied -> params unchanged
            np.testing.assert_array_equal(np.asarray(p1[k]), before[k])
        # the emitted pending grads are the true global average
        g_ref = jax.grad(mse_loss)(mlp_init(8), x, y)
        for k in g_ref:
            np.testing.assert_allclose(
                np.asarray(pending[k]), np.asarray(g_ref[k]), rtol=1e-5, atol=1e-6
            )

    def test_stale_gradient_training_converges(self, comm):
        x, y = make_data(n=16 * comm.size, seed=3)
        dp = DataParallel(mlp_apply, comm=comm, optimizer=optax.sgd(5e-2))
        step = dp.make_train_step(mse_loss)
        p = jax.device_put(mlp_init(8, seed=2), comm.replicated())
        s = dp.optimizer.init(p)
        pending = dp.init_pending(p)
        xb, yb = dp.shard_batch(x, y)
        first = last = None
        for i in range(60):
            p, s, pending, loss = step(p, s, pending, xb, yb)
            if i == 0:
                first = float(loss)
            last = float(loss)
        assert last < first * 0.5, (first, last)

    def test_second_step_matches_blocking_first_update(self, comm):
        # nonblocking step 2 applies exactly the grads blocking step 1 applies
        x, y = make_data(seed=5)
        # on the host: each step donates the parameters it is given
        p0 = jax.tree.map(np.asarray, mlp_init(8, seed=5))
        opt = optax.sgd(0.1)

        dpb = DataParallel(
            mlp_apply, comm=comm, optimizer=opt, blocking_parameter_updates=True
        )
        bstep = dpb.make_train_step(mse_loss)
        pb = jax.device_put(p0, comm.replicated())
        sb = opt.init(pb)
        xb, yb = dpb.shard_batch(x, y)
        pb1, sb, _ = bstep(pb, sb, xb, yb)

        dpn = DataParallel(mlp_apply, comm=comm, optimizer=opt)
        nstep = dpn.make_train_step(mse_loss)
        pn = jax.device_put(p0, comm.replicated())
        sn = opt.init(pn)
        pend = dpn.init_pending(pn)
        pn, sn, pend, _ = nstep(pn, sn, pend, xb, yb)   # applies zeros
        pn, sn, pend, _ = nstep(pn, sn, pend, xb, yb)   # applies step-1 grads
        for k in pb1:
            np.testing.assert_allclose(
                np.asarray(pn[k]), np.asarray(pb1[k]), rtol=1e-5, atol=1e-6
            )


class TestDataParallelOptimizer:
    def test_step_applies_update(self):
        opt = DataParallelOptimizer(optax.sgd(0.5))
        params = {"w": jnp.ones((3,))}
        state = opt.init(params)
        grads = {"w": jnp.ones((3,))}
        new_params, state = opt.step(params, state, grads)
        np.testing.assert_allclose(np.asarray(new_params["w"]), 0.5)
        opt.zero_grad()  # no-op

    def test_rejects_non_optax(self):
        with pytest.raises(TypeError):
            DataParallelOptimizer(object())


class TestDASO:
    def _run(self, daso, params, x, y, epochs, batches_per_epoch, bs):
        daso.set_loss(mse_loss)
        daso.last_batch = batches_per_epoch - 1
        sp = daso.stack_params(params)
        so = daso.init(sp)
        losses = []
        for e in range(epochs):
            ep_loss = 0.0
            for b in range(batches_per_epoch):
                lo = (b * bs) % x.shape[0]
                xb, yb = x[lo : lo + bs], y[lo : lo + bs]
                sp, so, loss = daso.step(sp, so, (xb, yb))
                ep_loss += float(loss)
            daso.epoch_loss_logic(ep_loss / batches_per_epoch)
            losses.append(ep_loss / batches_per_epoch)
        return daso.unstack_params(sp), losses

    def test_warmup_matches_blocking_dp(self, comm):
        # during warmup DASO is full blocking sync: must track plain DP
        x, y = make_data()
        params0 = mlp_init(8)
        opt = optax.sgd(0.1)

        daso = DASO(opt, total_epochs=10, comm=comm, verbose=False)
        assert daso.n_nodes * daso.n_local == comm.size
        daso.set_loss(mse_loss)
        daso.last_batch = 0
        sp = daso.stack_params(params0)
        so = daso.init(sp)
        sp, so, loss = daso.step(sp, so, (x, y))
        got = daso.unstack_params(sp)

        g = jax.grad(mse_loss)(params0, x, y)
        s0 = opt.init(params0)
        u, _ = opt.update(g, s0, params0)
        want = optax.apply_updates(params0, u)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), rtol=1e-4, atol=1e-5
            )

    def test_full_schedule_trains(self, comm):
        # run through warmup -> cycling -> cooldown; loss must decrease and
        # params must be finite & synchronized at the end
        x, y = make_data()
        daso = DASO(
            optax.adam(5e-3), total_epochs=8, comm=comm,
            warmup_epochs=2, cooldown_epochs=2, max_global_skips=4,
        )
        params, losses = self._run(
            daso, mlp_init(8, seed=2), x, y, epochs=8, batches_per_epoch=4,
            bs=2 * comm.size,
        )
        assert losses[-1] < losses[0]
        for leaf in jax.tree.leaves(params):
            assert bool(jnp.isfinite(leaf).all())

    def test_gs1_drains_payload_queue(self, comm):
        # with global_skip=1 every batch is a sync batch; pending payloads
        # must be drained, not accumulated
        x, y = make_data()
        daso = DASO(optax.sgd(0.05), total_epochs=10, comm=comm)
        daso.set_loss(mse_loss)
        daso.last_batch = 7
        daso.global_skip, daso.local_skip, daso.batches_to_wait = 1, 1, 1
        sp = daso.stack_params(mlp_init(8))
        so = daso.init(sp)
        bs = comm.size
        for b in range(8):
            lo = (b * bs) % x.shape[0]
            sp, so, _ = daso.step(sp, so, (x[lo : lo + bs], y[lo : lo + bs]))
            assert len(daso._prev_params) <= 1
        assert len(daso._prev_params) <= 1

    def test_scheduler_scales_updates(self, comm):
        # a zero schedule must freeze training entirely
        zero_sched = lambda step: 0.0
        daso = DASO(
            optax.sgd(1.0), total_epochs=4, comm=comm, scheduler=zero_sched
        )
        daso.set_loss(mse_loss)
        daso.last_batch = 0
        x, y = make_data(n=4 * comm.size)
        p0 = mlp_init(8)
        sp = daso.stack_params(p0)
        so = daso.init(sp)
        sp, so, _ = daso.step(sp, so, (x, y))
        got = daso.unstack_params(sp)
        for k in p0:
            # atol: unstack's f32 replica mean costs ~1 ulp even on
            # bit-identical replicas
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(p0[k]), atol=1e-6
            )

    def test_absolute_lr_scheduler_not_double_applied(self, comm):
        # an absolute-lr schedule (lr_scheduler factory output) passed with
        # scheduler_base_lr is divided by the base lr: a constant absolute
        # schedule at exactly the base lr must match no scheduler at all
        x, y = make_data(n=4 * comm.size)
        p0 = mlp_init(8)

        def one_step(sched, base=None):
            daso = DASO(optax.sgd(0.5), total_epochs=4, comm=comm,
                        scheduler=sched, scheduler_base_lr=base)
            daso.set_loss(mse_loss)
            daso.last_batch = 0
            sp = daso.stack_params(p0)
            so = daso.init(sp)
            sp, so, _ = daso.step(sp, so, (x, y))
            return daso.unstack_params(sp)

        got = one_step(lr_scheduler.ConstantLR(0.5, factor=1.0, total_iters=1), 0.5)
        want = one_step(None)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6
            )

    def test_warmup_ramp_scheduler_exact(self, comm):
        # an absolute-lr warmup ramp (start_factor<1) with scheduler_base_lr
        # must scale the first update by exactly start_factor — not by
        # ramp(0)/ramp-normalized 1.0 (the s0-normalization bug)
        x, y = make_data(n=4 * comm.size)
        p0 = mlp_init(8)
        lr = 0.5

        def one_step(sched, base):
            daso = DASO(optax.sgd(lr), total_epochs=4, comm=comm,
                        scheduler=sched, scheduler_base_lr=base)
            daso.set_loss(mse_loss)
            daso.last_batch = 0
            sp = daso.stack_params(p0)
            so = daso.init(sp)
            sp, so, _ = daso.step(sp, so, (x, y))
            return daso.unstack_params(sp)

        ramp = lr_scheduler.LinearLR(lr, start_factor=1.0 / 4, total_iters=10)
        got = one_step(ramp, lr)
        # oracle: plain sgd with lr/4 for the first step
        ref = one_step(lambda step: 0.25, None)
        for k in ref:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(ref[k]), rtol=1e-5, atol=1e-6
            )

    def test_gs8_hold_gates_plateau_decay(self, comm):
        # at max global skip the schedule must hold for _gs8_waits epochs
        # before plateau-driven decay can act
        daso = DASO(
            optax.sgd(0.1), total_epochs=40, comm=comm,
            warmup_epochs=0, cooldown_epochs=0, max_global_skips=8,
        )
        daso.epoch = 1  # past warmup
        daso.global_skip, daso.local_skip, daso.batches_to_wait = 8, 2, 2

        # prime the detector ONCE so the next call reports a plateau; the
        # hold must re-arm consumed triggers so decay fires exactly when the
        # hold expires, with no fresh patience window
        daso.stability.best = 1.0
        daso.stability.num_bad_epochs = daso.stability.patience

        for i in range(daso._gs8_waits - 1):
            daso.epoch_loss_logic(1.0)
            assert daso.global_skip == 8, f"decayed early at hold epoch {i}"
            daso.epoch += 1
        daso.epoch_loss_logic(1.0)  # hold expired -> decay acts immediately
        assert daso.global_skip < 8

    def test_rejects_bad_scheduler(self, comm):
        with pytest.raises(TypeError):
            DASO(optax.sgd(0.1), total_epochs=2, comm=comm, scheduler=3)

    def test_rejects_bad_device_factor(self, comm):
        if comm.size % 3 != 0:
            with pytest.raises(ValueError):
                DASO(optax.sgd(0.1), total_epochs=2, comm=comm, n_nodes=3)

    def test_requires_last_batch(self, comm):
        daso = DASO(optax.sgd(0.1), total_epochs=2, comm=comm)
        daso.set_loss(mse_loss)
        with pytest.raises(ValueError, match="last_batch"):
            daso.step({}, {}, (jnp.zeros((8, 8)), jnp.zeros((8, 1))))


class TestDataParallelMultiGPU:
    def test_binds_model(self, comm):
        daso = DASO(optax.sgd(0.1), total_epochs=2, comm=comm)
        net = DataParallelMultiGPU(mlp_apply, daso)
        assert daso.module is mlp_apply
        params = mlp_init(8)
        x, _ = make_data(n=2 * comm.size)
        out = net(params, x)
        assert out.shape == (2 * comm.size, 1)


class TestDetectMetricPlateau:
    def test_min_mode_plateau(self):
        det = DetectMetricPlateau(patience=2, threshold=0.0, threshold_mode="abs")
        assert not det.test_if_improving(1.0)
        assert not det.test_if_improving(1.0)  # bad 1
        assert not det.test_if_improving(1.0)  # bad 2
        assert det.test_if_improving(1.0)      # bad 3 > patience -> plateau

    def test_improvement_resets(self):
        det = DetectMetricPlateau(patience=1, threshold=0.0, threshold_mode="abs")
        assert not det.test_if_improving(1.0)
        assert not det.test_if_improving(0.5)
        assert not det.test_if_improving(0.9)
        assert not det.test_if_improving(0.25)
        assert det.num_bad_epochs == 0

    def test_state_roundtrip(self):
        det = DetectMetricPlateau(patience=3)
        det.test_if_improving(2.0)
        state = det.get_state()
        det2 = DetectMetricPlateau()
        det2.set_state(state)
        assert det2.best == det.best
        assert det2.patience == 3

    def test_max_mode(self):
        det = DetectMetricPlateau(mode="max", patience=1, threshold=0.0,
                                  threshold_mode="abs")
        assert not det.test_if_improving(0.1)
        assert not det.test_if_improving(0.05)
        assert det.test_if_improving(0.05)

    def test_rejects_bad_mode(self):
        with pytest.raises(ValueError):
            DetectMetricPlateau(mode="sideways")


class TestLRSchedulers:
    def test_step_lr(self):
        sched = lr_scheduler.StepLR(1.0, step_size=10, gamma=0.1)
        assert float(sched(0)) == pytest.approx(1.0)
        assert float(sched(10)) == pytest.approx(0.1)
        assert float(sched(20)) == pytest.approx(0.01)

    def test_cosine(self):
        sched = lr_scheduler.CosineAnnealingLR(1.0, T_max=100)
        assert float(sched(0)) == pytest.approx(1.0)
        assert float(sched(100)) == pytest.approx(0.0, abs=1e-6)

    def test_linear(self):
        sched = lr_scheduler.LinearLR(1.0, start_factor=0.5, total_iters=10)
        assert float(sched(0)) == pytest.approx(0.5)
        assert float(sched(10)) == pytest.approx(1.0)

    def test_optax_passthrough(self):
        import heat_tpu

        opt = heat_tpu.optim.adam(1e-3)
        assert hasattr(opt, "update")

    def test_nn_passthrough(self):
        import heat_tpu

        dense = heat_tpu.nn.Dense
        import flax.linen

        assert dense is flax.linen.Dense

    def test_functional_passthrough(self):
        import heat_tpu

        assert heat_tpu.nn.functional.relu is jax.nn.relu
