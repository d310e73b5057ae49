"""Tests for the Pallas flash-attention kernel.

On the CPU test mesh the kernel runs under the Pallas interpreter
(``interpret=True`` is the off-TPU default), so these exercise the exact
kernel program — grid, BlockSpecs, scratch carries — that compiles to
Mosaic on a real chip. Oracle: the dense numpy attention from
tests/test_parallel.py plus the XLA online-softmax path it must match.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.parallel import flash_attention, local_attention
from tests.test_parallel import dense_attention, make_qkv


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv(2, 96, 2, 16)
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=causal, block_q=32, block_k=32,
        )
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    def test_matches_local_attention_bitpattern(self):
        # same f32 online softmax as the XLA path — agreement should be tight
        q, k, v = make_qkv(1, 64, 2, 32, seed=3)
        a = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_q=32, block_k=32,
        )
        b = local_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), block_size=32
        )
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)

    def test_ragged_seq_and_headdim(self):
        # T not a block multiple, D not lane-aligned — wrapper pads, output
        # sliced back; K tail padding must not leak into the softmax
        q, k, v = make_qkv(1, 50, 2, 24, seed=5)
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_q=32, block_k=32,
        )
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    def test_kv_valid_masks_padding(self):
        q, k, v = make_qkv(1, 64, 2, 16, seed=7)
        valid = 40
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            kv_valid=valid, block_q=32, block_k=32,
        )
        ref = dense_attention(q, k, v, valid=valid)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    def test_causal_first_row_defined(self):
        # causal row 0 attends only to k 0 — fully-masked guard must not NaN
        q, k, v = make_qkv(1, 32, 1, 16, seed=9)
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, block_q=16, block_k=16,
        )
        assert np.isfinite(np.asarray(out)).all()

    def test_cross_attention_lengths(self):
        # Tq != Tk exercises independent q/k grids
        rng = np.random.default_rng(11)
        q = rng.standard_normal((2, 48, 2, 16)).astype(np.float32)
        k = rng.standard_normal((2, 80, 2, 16)).astype(np.float32)
        v = rng.standard_normal((2, 80, 2, 16)).astype(np.float32)
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_q=16, block_k=32,
        )
        s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bkhd->bqhd", p, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    def test_grad_flows(self):
        # custom_vjp backward recomputes through the XLA path
        q, k, v = make_qkv(1, 32, 2, 16, seed=13)
        qj, kj, vj = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

        def loss(q_, k_, v_):
            return flash_attention(q_, k_, v_, block_q=16, block_k=16).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(qj, kj, vj)

        def ref_loss(q_, k_, v_):
            return local_attention(q_, k_, v_, block_size=16).sum()

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(qj, kj, vj)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), rtol=1e-4, atol=1e-5)

    def test_bf16_inputs(self):
        q, k, v = make_qkv(1, 64, 2, 16, seed=17)
        out = flash_attention(
            jnp.asarray(q, dtype=jnp.bfloat16),
            jnp.asarray(k, dtype=jnp.bfloat16),
            jnp.asarray(v, dtype=jnp.bfloat16),
            block_q=32, block_k=32,
        )
        assert out.dtype == jnp.bfloat16
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), ref, rtol=0.05, atol=0.05
        )


class TestUlyssesPallas:
    def test_ulysses_pallas_matches_dense(self):
        import heat_tpu as ht

        comm = ht.get_comm()
        p = comm.size
        b, t, h, d = 2, 4 * p, p, 8
        q, k, v = make_qkv(b, t, h, d, seed=21)
        sharding = comm.sharding(1, 4)
        from heat_tpu.parallel import ulysses_attention

        out = ulysses_attention(
            jax.device_put(jnp.asarray(q), sharding),
            jax.device_put(jnp.asarray(k), sharding),
            jax.device_put(jnp.asarray(v), sharding),
            comm=comm, use_pallas=True,
        )
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


class TestPallasBackwardKernels:
    """Round-4 (VERDICT r3 item 5): the backward pass is two Pallas kernels
    (dq; dk/dv) from the saved O/log-sum-exp — oracle is autodiff through
    the XLA online-softmax path."""

    def _grads(self, fn, q, k, v, g):
        def loss(q_, k_, v_):
            return (fn(q_, k_, v_).astype(jnp.float32) * g.astype(jnp.float32)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize(
        "b,t,h,d,causal,kv_valid",
        [
            (1, 256, 2, 64, False, None),
            (2, 384, 2, 32, True, None),
            (1, 300, 1, 64, False, 260),
            (1, 128, 2, 128, True, 100),
        ],
    )
    def test_f32_grads_match_xla_path(self, b, t, h, d, causal, kv_valid):
        from heat_tpu.parallel import flash_attention
        from heat_tpu.parallel.attention import local_attention

        rng = np.random.default_rng(t + d)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
            for _ in range(4)
        )
        gf = self._grads(
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=causal, kv_valid=kv_valid, interpret=True
            ),
            q, k, v, g,
        )
        gr = self._grads(
            lambda q_, k_, v_: local_attention(
                q_, k_, v_, causal=causal, kv_valid=kv_valid
            ),
            q, k, v, g,
        )
        for name, a, bb in zip("qkv", gf, gr):
            err = float(jnp.abs(a - bb).max())
            ref = max(float(jnp.abs(bb).max()), 1.0)
            assert err < 2e-3 * ref, (name, err, ref)

    def test_bf16_grads_close(self):
        from heat_tpu.parallel import flash_attention
        from heat_tpu.parallel.attention import local_attention

        rng = np.random.default_rng(5)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.bfloat16)
            for _ in range(4)
        )
        gf = self._grads(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True, interpret=True),
            q, k, v, g,
        )
        gr = self._grads(
            lambda q_, k_, v_: local_attention(q_, k_, v_, causal=True),
            q, k, v, g,
        )
        for name, a, bb in zip("qkv", gf, gr):
            af, bf = a.astype(jnp.float32), bb.astype(jnp.float32)
            rel = float(jnp.abs(af - bf).max()) / max(float(jnp.abs(bf).max()), 1.0)
            assert rel < 0.1, (name, rel)


class TestFusedBackward:
    """The fused single-pass backward must produce the SAME grads as the
    two-pass kernels (shared `_rebuild_probs`; only the accumulation
    schedule differs — f32 dK, dV resident vs per-pass scratch)."""

    def _grads(self, fn, q, k, v, g):
        def loss(q_, k_, v_):
            return (fn(q_, k_, v_).astype(jnp.float32) * g.astype(jnp.float32)).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize(
        "b,t,h,d,causal,kv_valid",
        [
            (1, 256, 2, 64, False, None),
            (2, 384, 2, 32, True, None),   # ragged t -> q/k pad rows
            (1, 256, 2, 64, True, 200),    # kv padding mask
        ],
    )
    def test_fused_matches_two_pass_f32(self, b, t, h, d, causal, kv_valid):
        from heat_tpu.parallel import flash_attention

        rng = np.random.default_rng(17)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
            for _ in range(4)
        )
        kw = dict(causal=causal, kv_valid=kv_valid, interpret=True,
                  block_q=128, block_k=128)
        g2 = self._grads(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, bwd_impl="two_pass", **kw),
            q, k, v, g,
        )
        gf = self._grads(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, bwd_impl="fused", **kw),
            q, k, v, g,
        )
        for name, a, bb in zip("qkv", gf, g2):
            err = float(jnp.abs(a - bb).max())
            ref = max(float(jnp.abs(bb).max()), 1.0)
            # identical math modulo f32 summation order
            assert err < 1e-5 * ref, (name, err, ref)

    def test_auto_resolves_and_matches(self):
        from heat_tpu.parallel import flash_attention
        from heat_tpu.parallel.pallas_attention import (
            _bwd_takes_fused,
            _flash_bwd_fused,
        )
        import heat_tpu.parallel.pallas_attention as pa

        # "auto" must actually take the fused branch at this shape (the
        # grads comparison alone would pass even if dispatch regressed to
        # two_pass — record the fused driver running)
        assert _bwd_takes_fused(256, 256, 64, 2, 128, 128)
        calls = []
        orig = _flash_bwd_fused

        def spy(*a, **kw):
            calls.append(1)
            return orig(*a, **kw)

        pa._flash_bwd_fused = spy
        rng = np.random.default_rng(23)
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.bfloat16)
            for _ in range(4)
        )
        kw = dict(causal=True, interpret=True, block_q=128, block_k=128)
        g2 = self._grads(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, bwd_impl="two_pass", **kw),
            q, k, v, g,
        )
        ga = self._grads(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, bwd_impl="auto", **kw),
            q, k, v, g,
        )
        pa._flash_bwd_fused = orig
        assert calls, "auto did not dispatch to the fused backward"
        for name, a, bb in zip("qkv", ga, g2):
            af, bf = a.astype(jnp.float32), bb.astype(jnp.float32)
            rel = float(jnp.abs(af - bf).max()) / max(float(jnp.abs(bf).max()), 1.0)
            # bf16 cast points differ only in dQ's final rounding
            assert rel < 2e-2, (name, rel)

    def test_bad_impl_raises(self):
        from heat_tpu.parallel import flash_attention

        q = jnp.zeros((1, 8, 1, 8), jnp.float32)
        with pytest.raises(ValueError, match="bwd_impl"):
            flash_attention(q, q, q, bwd_impl="nope", interpret=True)
