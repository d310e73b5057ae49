"""Gated DeltaNet — the linear-attention mixer of Qwen3-Next (Yang et al.,
"Gated Delta Networks", arXiv:2412.06464; HF ``modeling_qwen3_next.py``).

Per value head, with a state ``S`` of ``head_k_dim x head_v_dim`` that starts
at zero, a decay ``alpha_t`` in (0, 1) and a write strength ``beta_t``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

:func:`gated_delta_rule` computes it in chunks of positions (HF's
``torch_chunk_gated_delta_rule``): inside a chunk the writes depend on each
other through a unit lower-triangular system, solved for all chunks at once;
between chunks one ``lax.scan`` carries ``S``. What the scan does is two small
products a chunk; everything else (the chunk's own scores, the solve, the
read of the state by the queries) is batched over the chunks outside it. The
backward pass is the autodiff of that form: the scan keeps the state once a
chunk (``T / chunk`` states, not ``T``) and the solve keeps its result alone.

Float32 whatever ``dtype`` says: the decay and its running sum, ``beta``,
the l2 norms, the state, the triangular solve, the gated norm. The
projections and the chunk products take ``dtype`` operands and accumulate in
float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

CHUNK = 64


def l2_normalise(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


_HIGHEST = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a (..., C, C)``,
    float32: ``a`` is nilpotent, so the inverse is the finite sum of
    ``(-a)^j``, taken as the product of ``I + (-a)^(2^i)``: two products a
    doubling and no loop over rows. Its transpose needs the inverse alone
    (``-x^T g x^T``), so none of the powers is kept."""
    c = a.shape[-1]
    power = -a
    inv = jnp.eye(c, dtype=a.dtype) + power
    for _ in range(max(math.ceil(math.log2(c)) - 1, 0)):
        power = _HIGHEST(power, power)
        inv = inv + _HIGHEST(inv, power)
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, g):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-_HIGHEST(_HIGHEST(inv_t, g), inv_t),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, dtype: Any = jnp.float32):
    """The gated delta rule over ``q, k (B, T, H, Dk)``, ``v (B, T, H, Dv)``,
    ``g`` = ``log(alpha)`` and ``beta`` ``(B, T, H)``; returns ``o (B, T, H,
    Dv)`` in float32. ``q`` and ``k`` arrive normalised and scaled. A length
    that is no multiple of ``chunk`` is padded with positions that neither
    decay nor write (``g = 0``, ``beta = 0``)."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):  # (B, T, H, ...) -> (N, B, H, C, ...)
        a = a.reshape((b, n, chunk, h) + a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype), preferred_element_type=f32)

    q, k, v = chunks(q.astype(f32)), chunks(k.astype(f32)), chunks(v.astype(f32))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))  # (N, B, H, C)
    run = jnp.cumsum(g, axis=-1)  # the log of the decay since the chunk began
    rows = jnp.arange(chunk)
    at_or_below = rows[:, None] >= rows[None, :]
    gap = run[..., :, None] - run[..., None, :]
    decay = jnp.where(at_or_below, jnp.exp(jnp.where(at_or_below, gap, 0.0)), 0.0)  # (N, B, H, C, C)

    k_beta = k * beta[..., None]
    inside = jnp.where(rows[:, None] > rows[None, :], mm("...id,...jd->...ij", k_beta, k) * decay, 0.0)
    solve = _unit_lower_inverse(inside)
    writes = mm("...ij,...jd->...id", solve, v * beta[..., None])  # at a zero state
    reads = mm("...ij,...jd->...id", solve, k_beta * jnp.exp(run)[..., None])  # what the state takes off them
    to_end = k * jnp.exp(run[..., -1:] - run)[..., None]
    keep = jnp.exp(run[..., -1])  # (N, B, H): the chunk's whole decay

    def step(state, xs):
        reads_i, writes_i, to_end_i, keep_i = xs
        new = writes_i - mm("bhcd,bhde->bhce", reads_i, state)
        after = state * keep_i[..., None, None] + mm("bhcd,bhce->bhde", to_end_i, new)
        return after, (state, new)

    _, (states, new) = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), (reads, writes, to_end, keep))
    scores = mm("...id,...jd->...ij", q, k) * decay
    o = mm("...id,...de->...ie", q * jnp.exp(run)[..., None], states) + mm("...ij,...je->...ie", scores, new)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # (B, N, C, H, Dv)
    return o.reshape(b, t + pad, h, dv)[:, :t]


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]`` over ``x (B, T, C)``
    with ``w (C, K)``: a depthwise convolution that sees no later position
    (positions before the first count as zero) and has no bias."""
    taps = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps))


@jax.custom_vjp
def conv_silu(x, w):
    """``silu(causal_depthwise_conv(x, w))`` in float32. The backward pass
    keeps ``x`` and ``w`` alone and is written out, three passes over the
    channels (the pre-activation again, its cotangent carried to the input by
    the same taps reversed, and a sum over positions a tap): autodiff would
    hold a shifted copy of every channel for every tap."""
    return nn.silu(causal_depthwise_conv(x.astype(jnp.float32), w))


def _conv_silu_fwd(x, w):
    return conv_silu(x, w), (x, w)


def _conv_silu_bwd(res, g):
    x, w = res
    taps, t = w.shape[1], x.shape[1]
    x32 = x.astype(jnp.float32)
    pre = causal_depthwise_conv(x32, w)
    gate = jax.nn.sigmoid(pre)
    g_pre = g * gate * (1.0 + pre * (1.0 - gate))
    later = jnp.pad(g_pre, ((0, 0), (0, taps - 1), (0, 0)))
    g_x = sum(later[:, taps - 1 - j: taps - 1 - j + t] * w[:, j] for j in range(taps))
    earlier = jnp.pad(x32, ((0, 0), (taps - 1, 0), (0, 0)))
    g_w = jnp.stack([jnp.sum(g_pre * earlier[:, j:j + t], axis=(0, 1)) for j in range(taps)], axis=1)
    return g_x.astype(x.dtype), g_w


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer, ``(B, T, D_model)`` in and out::

        [q, k, v, z] = x W_qkvz          [b, a] = x W_ba
        [q, k, v] = silu(causal depthwise conv of concat(q, k, v))
        q, k = l2-normalised over their head, q scaled by head_k_dim^-1/2;
               key head i serves value heads i*r .. i*r + r - 1
        beta = sigmoid(b)    alpha = exp(-exp(A_log) softplus(a + dt_bias))
        o = gated delta rule (above), RMSNorm over each head with a gain, times silu(z)
        out = o W_out

    ``W_qkvz`` is laid out ``[q | k | v | z]``, each head by head (HF
    interleaves the four by key head; a permutation of columns).
    ``matrix_init`` draws the input matrices, ``out_init`` the one that writes
    into the residual stream. A batch goes through a sequence at a time.
    """

    num_k_heads: int
    num_v_heads: int
    head_k_dim: int = 128
    head_v_dim: int = 128
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    accum_dtype: Optional[Any] = None
    chunk: int = CHUNK
    matrix_init: Any = None  # None: lecun_normal
    out_init: Any = None  # None: as matrix_init

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        hk, hv, dk, dv = self.num_k_heads, self.num_v_heads, self.head_k_dim, self.head_v_dim
        if hv % hk:
            raise ValueError(f"{hv} value heads do not divide over {hk} key heads")
        key_dim, value_dim = hk * dk, hv * dv
        out_dtype = self.dtype if self.accum_dtype is None else self.accum_dtype

        init = nn.initializers.lecun_normal() if self.matrix_init is None else self.matrix_init
        params = {
            "in_qkvz": self.param("in_qkvz", init, (d, 2 * key_dim + 2 * value_dim), jnp.float32),
            "in_ba": self.param("in_ba", init, (d, 2 * hv), jnp.float32),
            "conv": self.param(
                "conv", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=1, out_axis=0),
                (2 * key_dim + value_dim, self.conv_kernel), jnp.float32,
            ),
            "A_log": self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)), (hv,)
            ),
            "dt_bias": self.param("dt_bias", nn.initializers.ones, (hv,), jnp.float32),
            "norm": self.param("norm", nn.initializers.ones, (dv,), jnp.float32),
            "out": self.param("out", init if self.out_init is None else self.out_init, (value_dim, d), jnp.float32),
        }

        def project(x, w):
            return jnp.dot(x.astype(self.dtype), w.astype(self.dtype), preferred_element_type=out_dtype)

        def mix(p, x):  # x: (sequences, T, d)
            n = x.shape[0]
            with jax.named_scope("gdn.project"):
                # one matrix, read in two column ranges: no (tokens, 12,288) result to slice
                qkv = project(x, p["in_qkvz"][:, : 2 * key_dim + value_dim])
                z = project(x, p["in_qkvz"][:, 2 * key_dim + value_dim:])
                ba = project(x, p["in_ba"]).astype(jnp.float32)
            with jax.named_scope("gdn.conv"):
                qkv = conv_silu(qkv, p["conv"])
                q = l2_normalise(qkv[..., :key_dim].reshape(n, t, hk, dk)) * dk**-0.5
                k = l2_normalise(qkv[..., key_dim: 2 * key_dim].reshape(n, t, hk, dk))
                v = qkv[..., 2 * key_dim:].reshape(n, t, hv, dv)
                q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
            with jax.named_scope("gdn.scan"):
                beta = jax.nn.sigmoid(ba[..., :hv])
                g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
                o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk, dtype=self.dtype)
            with jax.named_scope("gdn.gate_norm"):
                o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps) * p["norm"]
                o = o * nn.silu(z.astype(jnp.float32).reshape(n, t, hv, dv))
            return project(o.reshape(n, t, value_dim), p["out"])

        if b == 1:
            return mix(params, x)
        # a sequence at a time, each recomputed in the backward pass: what the mixer
        # holds between its passes (a dozen arrays of positions x 8,192 channels and
        # the rule's chunk-by-chunk ones) is held for one sequence, not for the batch
        one = jax.checkpoint(lambda p, x: mix(p, x[None])[0])
        return jax.lax.map(functools.partial(one, params), x)
