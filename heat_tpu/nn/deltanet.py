"""Gated DeltaNet — the linear-attention mixer of Qwen3-Next (Yang et al.,
"Gated Delta Networks", arXiv:2412.06464; HF ``modeling_qwen3_next.py``).

Per value head, with a state ``S`` of ``head_k_dim x head_v_dim`` that starts
at zero, a decay ``alpha_t`` in (0, 1) and a write strength ``beta_t``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

:func:`gated_delta_rule` computes it in chunks of positions (HF's
``torch_chunk_gated_delta_rule``): inside a chunk the writes depend on each
other through a unit lower-triangular system; between chunks one ``lax.scan``
carries ``S``. All of a chunk's work is the body of that scan, one *chunk
step* ``(S, q_i, k_i, v_i, decays_i, beta_i) -> (S', o_i)``
(:func:`heat_tpu.nn.pallas_delta.chunk_step`): the chunk's decays, scores and
solve, what the state takes off the writes, the new state and the output. On a
TPU, at head sizes of whole lanes, the step is one Pallas kernel
(``delta_chunk_fwd``) that keeps everything of size chunk x chunk and chunk x
head in VMEM and reads a key head once for the value heads it serves;
elsewhere it is the same function as XLA's program. Outside the scan stay the
running sum of the log-decays and the split into chunks, which copies nothing
for one sequence. The backward pass is the scan's transpose, autodiff's, around
the step's own: a second kernel (``delta_chunk_bwd``) that takes ``(dS', do_i)``
and the step's inputs and forms the chunk's quantities again, so the forward
scan keeps its inputs and the state once a chunk (``T / chunk`` states, not
``T``) and no array of chunks x heads x chunk x chunk exists in either pass.

Why the scan stayed, with ``S`` through HBM once a chunk: the state a chunk
is what the backward pass needs anyway, and a loop's device event covers its
body, so the benchmark's reader goes on finding the rule by the loops that
carry ``S``. On a v5e a step is 21 us forward and 37 us backward at 32 heads of
128, nine tenths of it the kernel, which is bound by the solve's float32
products and not by what it moves.

Before the rule stand the causal depthwise convolution of the projection's ``q |
k | v`` channels, SiLU and the l2 norms of ``q`` and ``k``
(:func:`conv_silu_norm`): on a TPU, at the same head sizes, one more pair of
kernels (:mod:`heat_tpu.nn.pallas_gdn_conv`) that reads the projection once each
way; elsewhere :func:`conv_silu` and :func:`l2_normalise`, XLA's passes. All four
kernels are called through a module-level ``jax.jit``, so a model's mixers, in
each of their passes, share one trace and one lowering of each.

Float32 whatever ``dtype`` says: the decay and its running sum, ``beta``,
the l2 norms, the state, the triangular solve, the gated norm. The
projections and the chunk products take ``dtype`` operands and accumulate in
float32.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import telemetry
from . import pallas_gdn_conv
from .pallas_delta import chunk_step, kernel_chunk_step, takes_kernel

CHUNK = 64


def l2_normalise(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _xla_chunk_step(state, q, k, v, run, beta, dtype):
    """The chunk step in the kernel's layout (``pallas_delta.kernel_chunk_step``)
    as XLA's own program: :func:`pallas_delta.chunk_step` mapped over the
    sequences and the heads."""
    b, h, dk, dv = state.shape
    c = run.shape[-1]
    q, k = (jnp.repeat(a.reshape(b, c, -1, dk), h * dk // a.shape[-1], axis=2) for a in (q, k))
    over_heads = jax.vmap(functools.partial(chunk_step, dtype=dtype), in_axes=(0, 1, 1, 1, 0, 0), out_axes=(0, 1))
    after, o = jax.vmap(over_heads)(state, q, k, v.reshape(b, c, h, dv), run[:, :, None], beta[:, :, None])
    return after, o.reshape(b, c, h * dv)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, dtype: Any = jnp.float32):
    """The gated delta rule over ``q, k (B, T, Hk, Dk)``, ``v (B, T, H, Dv)``,
    ``g`` = ``log(alpha)`` and ``beta`` ``(B, T, H)``; returns ``o (B, T, H,
    Dv)`` in float32. ``q`` and ``k`` arrive normalised and scaled; key head
    ``i`` serves value heads ``i r .. i r + r - 1`` (``r = H / Hk``), with no
    repeated copy where the kernel runs. A length that is no multiple of
    ``chunk`` is padded with positions that neither decay nor write (``g = 0``,
    ``beta = 0``). The chunk step is the Pallas kernel where the shapes and the
    backend let it (``pallas_delta.takes_kernel``) and XLA's program otherwise;
    the counters ``gdn.rule.kernel`` and ``gdn.rule.xla`` say which a trace took."""
    if takes_kernel(q.shape, v.shape, chunk):
        telemetry.get_registry().add("gdn.rule.kernel")
        step = functools.partial(kernel_chunk_step, dtype=dtype, interpret=False)
    else:
        telemetry.get_registry().add("gdn.rule.xla")
        # as the kernel's backward does, the XLA form keeps a step's inputs alone
        step = jax.checkpoint(functools.partial(_xla_chunk_step, dtype=dtype))
    return _chunked_rule(step, q, k, v, g, beta, chunk)


def _chunked_rule(step, q, k, v, g, beta, chunk):
    """``step`` (either form of the chunk step) over the chunks in turn, the
    state ``(B, H, Dk, Dv)`` carried from zero by one ``lax.scan``."""
    f32 = jnp.float32
    b, t, hk, dk = q.shape
    h, dv = v.shape[2:]
    if h % hk:
        raise ValueError(f"{h} value heads do not divide over {hk} key heads")
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):  # (B, T, ...) -> (N, B, C, the rest as one axis): no copy for one sequence
        return jnp.moveaxis(a.astype(f32).reshape(b, n, chunk, -1), 1, 0)

    # the log of the decay since the chunk began, and beta: (N, B, H, C)
    run = jnp.swapaxes(jnp.cumsum(chunks(g), axis=2), 2, 3)
    xs = (chunks(q), chunks(k), chunks(v), run, jnp.swapaxes(chunks(beta), 2, 3))
    _, o = jax.lax.scan(lambda state, x: step(state, *x), jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, dv)[:, :t]


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]`` over ``x (B, T, C)``
    with ``w (C, K)``: a depthwise convolution that sees no later position
    (positions before the first count as zero) and has no bias."""
    taps = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[:, j] for j in range(taps))


def _conv_pullback(g, x, w):
    """``g``, the cotangent of ``causal_depthwise_conv(x, w)``, pulled back to
    ``x`` (the same taps reversed) and to ``w`` (a sum over positions a tap)."""
    taps, t = w.shape[1], x.shape[1]
    later = jnp.pad(g, ((0, 0), (0, taps - 1), (0, 0)))
    g_x = sum(later[:, taps - 1 - j: taps - 1 - j + t] * w[:, j] for j in range(taps))
    earlier = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    g_w = jnp.stack([jnp.sum(g * earlier[:, j:j + t], axis=(0, 1)) for j in range(taps)], axis=1)
    return g_x, g_w


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
    x32 = x.astype(jnp.float32)
    pre = causal_depthwise_conv(x32, w)
    gate = jax.nn.sigmoid(pre)
    g_x, g_w = _conv_pullback(g * gate * (1.0 + pre * (1.0 - gate)), x32, w)
    return g_x.astype(x.dtype), g_w


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@jax.custom_vjp
def gated_short_conv(b, c, x, w):
    """``c * causal_depthwise_conv(b * x, w)`` in float32: the short
    convolution of the LFM2 family between its two multiplicative gates, ``b``,
    ``c`` and ``x`` ``(B, T, C)``, ``w (C, K)``, no bias and no activation. The
    backward pass keeps ``b``, ``c``, ``x`` and ``w`` alone and is written out
    (the gated input and the convolution again, the cotangent carried to the
    input by the same taps reversed, a sum over positions a tap): autodiff
    would hold a shifted copy of every channel for every tap."""
    f32 = jnp.float32
    return c.astype(f32) * causal_depthwise_conv(b.astype(f32) * x.astype(f32), w)


def _gated_short_conv_fwd(b, c, x, w):
    return gated_short_conv(b, c, x, w), (b, c, x, w)


def _gated_short_conv_bwd(res, g):
    b, c, x, w = res
    b32, c32, x32 = (a.astype(jnp.float32) for a in (b, c, x))
    z = b32 * x32
    g_z, g_w = _conv_pullback(g * c32, z, w)
    g_c = g * causal_depthwise_conv(z, w)
    return (g_z * x32).astype(b.dtype), g_c.astype(c.dtype), (g_z * b32).astype(x.dtype), g_w


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def conv_silu_norm(x, w, hk: int, dk: int, hv: int, dv: int):
    """The mixer's pass before the rule: ``x (B, T, C)``, the projection's ``[q
    | k | v]`` channels, through :func:`conv_silu` with taps ``w (C, K)``, then
    ``q`` and ``k`` l2-normalised over their head and ``q`` scaled by
    ``dk^-1/2``. Returns ``q, k (B, T, hk, dk)`` and ``v (B, T, hv, dv)`` in
    float32. One Pallas kernel each way where the shapes and the backend let it
    (``pallas_gdn_conv.takes_kernel``), which reads ``x`` once; XLA's passes
    otherwise; the counters ``gdn.conv.kernel`` and ``gdn.conv.xla`` say which
    a trace took."""
    b, t, _ = x.shape
    key_dim = hk * dk
    heads = lambda a: a.reshape(b, t, hk, dk)  # noqa: E731
    if pallas_gdn_conv.takes_kernel(x.shape, x.dtype, w.shape, hk, dk, hv, dv):
        telemetry.get_registry().add("gdn.conv.kernel")
        q, k, v = pallas_gdn_conv.conv_silu_norm(x, w, hk, dk, pallas_gdn_conv.ROWS, False)
        q, k = heads(q), heads(k)
    else:
        telemetry.get_registry().add("gdn.conv.xla")
        qkv = conv_silu(x, w)
        q = l2_normalise(heads(qkv[..., :key_dim])) * dk**-0.5
        k = l2_normalise(heads(qkv[..., key_dim: 2 * key_dim]))
        v = qkv[..., 2 * key_dim:]
    return q, k, v.reshape(b, t, hv, dv)


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
                q, k, v = conv_silu_norm(qkv, p["conv"], hk, dk, hv, dv)
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
