"""Transformer blocks over the device mesh — the flagship model family.

The reference framework has no transformer/attention code at all (SURVEY
§2.5: "no transformer code"); its parallelism mechanisms (ring schedule,
axis-aware Alltoall) are exactly what long-context attention is made of.
This module is the capability those mechanisms exist for, built TPU-first
as flax modules:

* :class:`TransformerBlock` — pre-LN block: attention (XLA online-softmax,
  the Pallas flash kernel, or a sequence-parallel schedule) + SwiGLU MLP.
* :class:`TransformerLM` — embedding → N blocks → final LN → logit
  projection; a complete causal LM forward.

Parallelism is selected by ``attn_impl``:

- ``"local"`` — single-shard XLA blockwise attention.
- ``"flash"`` — the hand-tiled Pallas kernel
  (:func:`heat_tpu.parallel.flash_attention`); with ``comm=`` it runs per
  batch shard (data parallel over the mesh).
- ``"ring"`` / ``"ulysses"`` — sequence-parallel over a mesh axis, for
  sequences sharded with :class:`heat_tpu.MeshCommunication` (pass
  ``comm=``). Ring keeps K/V moving over ICI; ulysses swaps sequence↔heads
  with two all_to_alls.

The architecture is a set of fields, not a model file: ``norm``
(``"layernorm"`` | ``"rmsnorm"`` | ``"rmsnorm_zero"``, the zero-centred form
``x rsqrt(mean x^2 + eps) (1 + w)``), ``positions`` (``"learned"`` |
``"rope"``) with ``rotary_fraction`` (the leading share of a head that is
rotated), ``qk_norm`` (over all of hidden, or ``"head"``: over each head),
``num_kv_heads`` and ``head_dim``, ``attn_gate`` (a sigmoid gate on the
attention output, projected beside the queries), ``mixers`` (the layer
pattern: which mixer the blocks of one period take, ``"attention"``,
``"deltanet"``, :mod:`heat_tpu.nn.deltanet`, sized by the ``gdn_*`` fields,
``"shortconv"``, :class:`GatedShortConv` with ``conv_taps`` taps, or
``"latent"``, :class:`LatentAttention` sized by ``latent``, a :class:`Latent`),
``ffn`` (``"swiglu"`` | ``"moe"``, the dropless top-k expert layer of
:mod:`heat_tpu.nn.moe`, with ``norm_topk``, ``norm_topk_eps``, ``shared_d_ff``,
``experts_held`` and ``held_window``; ``router_score``, ``route_scale``, ``router_bias`` and
``shared_gate`` for a sigmoid router whose choice a bias corrects and an
ungated shared expert), ``windows`` and ``rotary`` (one period each, beside
``mixers``: the sliding window of a block's attention, ``None`` = full, and
whether it rotates its queries and keys), ``sandwich_norm`` (four norms a
block: one more on each branch's output), ``embed_scale``, ``dense_layers``
and ``dense_d_ff`` (leading blocks with a SwiGLU of that width before the
expert blocks), ``tie_embeddings`` (the head is the embedding table: no
``lm_head``), ``mtp_modules`` (multi-token-prediction modules behind the
trunk, each a merge with the next token's embedding, one more block and a pass
through the same head), ``passes`` (a looped stack: the blocks and the final
norm run ``passes`` times on one set of weights, a one-output exit gate read
after every pass but the last), ``init_std`` and ``accum_dtype``. The defaults are the
pre-LN, learned-position, SwiGLU model this module began with, parameter tree
and numerics unchanged. :func:`olmoe_1b_7b`, :func:`qwen3_next_80b_a3b`,
:func:`trinity_mini`, :func:`lfm2_24b_a2b`, :func:`glm_4_7_flash` and :func:`ouro_2_6b` name the published configurations;
:func:`causal_lm_loss` is their training loss.

Weights are plain flax params — shard them with `jax.sharding` NamedSharding
(tp: column/row-split the Dense kernels; dp: replicate) exactly as any flax
model; the dryrun (`__graft_entry__.py`) exercises a dp×sp layout.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import telemetry
from ..parallel.pallas_attention import KEPT_RESIDUALS
from . import pallas_qk_prep
from .deltanet import GatedDeltaNet, gated_short_conv
from .functional import blocked_cross_entropy
from .moe import DroplessMoE, read_routing


def _dot_general(accum_dtype):
    """``lax.dot_general`` giving its result in ``accum_dtype`` (None: as
    flax does, the operands' dtype)."""
    if accum_dtype is None:
        return jax.lax.dot_general
    return functools.partial(jax.lax.dot_general, preferred_element_type=accum_dtype)


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean x^2 + eps) (1 + w)`` over the last axis in float32, ``w``
    starting at 0 (Qwen3-Next's norm); the result in ``dtype``."""

    epsilon: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon)
        return (y * (1.0 + w)).astype(self.dtype)


def _norm(kind, eps, dtype, name, **kw):
    if kind == "layernorm":
        return nn.LayerNorm(dtype=dtype, name=name, **({} if eps is None else {"epsilon": eps}))
    if kind == "rmsnorm":
        return nn.RMSNorm(dtype=dtype, name=name, epsilon=1e-6 if eps is None else eps, **kw)
    if kind == "rmsnorm_zero" and not kw:
        return ZeroCentredRMSNorm(1e-6 if eps is None else eps, dtype, name=name)
    raise ValueError(f"norm must be 'layernorm', 'rmsnorm' or 'rmsnorm_zero', got {kind!r}")


class _NormGain(nn.Module):
    """The gain of the RMS norm ``kind`` names, for a caller that runs the norm
    itself (:mod:`heat_tpu.nn.pallas_qk_prep`): the parameter ``scale`` the
    norm's own module would hold under this name, of that ``shape``, and for
    ``"rmsnorm_zero"`` one more than it."""

    kind: str

    @nn.compact
    def __call__(self, shape):
        zero = self.kind == "rmsnorm_zero"
        scale = self.param("scale", nn.initializers.zeros if zero else nn.initializers.ones, shape, jnp.float32)
        return 1.0 + scale if zero else scale


def _matrix_init(std):
    """normal(0, std) where a model states its matrices' deviation; None
    where it leaves them to each layer's own default."""
    return None if std is None else nn.initializers.normal(std)


def _given(init, key="kernel_init"):
    """``{key: init}`` for a flax layer, nothing where ``init`` is None."""
    return {} if init is None else {key: init}


def rotary(x, theta, fraction: float = 1.0):
    """Rotary positions on ``(B, T, H, D)`` in float32, the rotate-half form:
    ``x cos + (-x2, x1) sin`` with angles ``t * theta^(-2i/D)`` repeated over
    both halves. ``fraction`` < 1 rotates the first ``fraction * D`` of each
    head and leaves the rest as it is."""
    if fraction < 1.0:
        part = int(x.shape[-1] * fraction)
        return jnp.concatenate(
            [rotary(x[..., :part], theta), x[..., part:].astype(jnp.float32)], axis=-1
        )
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _attend(q, k, v, *, impl, causal, comm, block_size, flash_bwd_impl, window=None, head_major=False):
    """The attention core under the scope ``attn.full`` or, with a ``window``,
    ``attn.window``, ``(B, T, H, D)`` in and out; ``head_major``: ``q`` and
    ``k`` come ``(B, H, T, D)``, as :mod:`heat_tpu.nn.pallas_qk_prep` writes
    them for the flash kernels. The counters ``attn.full.kernel``, ``attn.window.kernel``
    (the Pallas kernels) and ``attn.window.xla`` (the masked XLA form) say once
    a trace which ran, ``attn.window.blocks_visited`` / ``.blocks_live`` what
    the windowed grid covers (:func:`heat_tpu.parallel.pallas_attention.window_grid`),
    ``attn.full.blocks_streamed`` / ``.blocks_live`` what a full causal layer's
    grid copies (:func:`~heat_tpu.parallel.pallas_attention.causal_grid`)."""
    with jax.named_scope("attn.full" if window is None else "attn.window"):
        return _attend_scoped(
            q, k, v, impl=impl, causal=causal, comm=comm, block_size=block_size,
            flash_bwd_impl=flash_bwd_impl, window=window, head_major=head_major,
        )


def _attend_scoped(q, k, v, *, impl, causal, comm, block_size, flash_bwd_impl, window, head_major):
    from ..parallel import (
        flash_attention,
        local_attention,
        ring_attention,
        ulysses_attention,
    )
    from ..parallel.pallas_attention import causal_grid, flash_attention_head_major, window_grid

    count = telemetry.get_registry().add
    if impl in ("ring", "ulysses") and window is not None:
        raise ValueError(
            f"attn_impl {impl!r} has no sliding window: a windowed layer takes 'flash' or 'local'"
        )
    if impl != "flash" and k.shape[2] != q.shape[2]:
        # only the flash kernels read a group's key-value head by index
        k, v = (jnp.repeat(a, q.shape[2] // k.shape[2], axis=2) for a in (k, v))
    if impl == "flash":
        # block_size None = the kernel's tuned tiles
        blocks = {} if block_size is None else {
            "block_q": block_size, "block_k": block_size,
        }
        form = dict(causal=causal, bwd_impl=flash_bwd_impl, window=window, **blocks)
        attend = functools.partial(flash_attention, **form)
        t_q, t_k = (a.shape[2 if head_major else 1] for a in (q, k))
        if window is None:
            count("attn.full.kernel")
            if causal:
                _, streamed, live = causal_grid(t_q, t_k, block_size, block_size)
                count("attn.full.blocks_streamed", streamed)
                count("attn.full.blocks_live", live)
        else:
            count("attn.window.kernel")
            visited, live = window_grid(t_q, t_k, window, block_size, block_size)
            count("attn.window.blocks_visited", visited)
            count("attn.window.blocks_live", live)
        if head_major:  # one device (``pallas_qk_prep.takes_kernel``); the values' transpose and the output's stay XLA's
            out = flash_attention_head_major(q, k, v.transpose(0, 2, 1, 3), **form)
            return out.transpose(0, 2, 1, 3)
        if comm is not None and comm.size > 1:
            # data parallel: the kernel runs on each chip's batch shard. A
            # bare pallas_call is opaque to the SPMD partitioner, which
            # would gather the sharded batch onto every chip to call it.
            spec = comm.spec(0, 4)
            return jax.shard_map(
                attend, mesh=comm.mesh, in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)
        return attend(q, k, v)
    if impl == "ring":
        # the ring processes one mesh chunk per hop; there is no block knob
        return ring_attention(q, k, v, comm=comm, causal=causal)
    if impl == "ulysses":
        return ulysses_attention(
            q, k, v, comm=comm, causal=causal,
            block_size=512 if block_size is None else block_size,
        )
    if window is not None:
        count("attn.window.xla")
    return local_attention(
        q, k, v, causal=causal, window=window,
        block_size=512 if block_size is None else block_size,
    )


class MultiHeadAttention(nn.Module):
    """QKV projection → blockwise attention → output projection.

    ``(B, T, D_model)`` in and out; the attention core runs in
    ``(B, T, H, D_head)`` layout shared by every impl, so switching
    single-chip ↔ sequence-parallel changes no weights.
    """

    num_heads: int
    attn_impl: str = "local"
    causal: bool = True
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    dtype: Any = jnp.float32
    # flash backward strategy (pallas_attention.flash_attention bwd_impl)
    flash_bwd_impl: str = "auto"
    # RMSNorm over all of d_model on the query and key projections, before
    # the split into heads; None = no such norm, else its epsilon
    qk_norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None  # rotary positions on q and k
    accum_dtype: Optional[Any] = None  # dtype of matmul results; None = dtype
    num_kv_heads: Optional[int] = None  # None = num_heads; fewer: grouped-query attention
    head_dim: Optional[int] = None  # None = d_model / num_heads
    # "head": a norm over each head's features, its gain shared by the heads,
    # in the form ``norm`` names; None: the norm over all of d_model (above)
    qk_norm_over: Optional[str] = None
    norm: str = "rmsnorm"
    rotary_fraction: float = 1.0
    gate: bool = False  # out = (attention * sigmoid(g)) W_o, g projected beside q
    matrix_init: Any = None
    out_init: Any = None
    window: Optional[int] = None  # position t sees t - window < j <= t; None: every j <= t

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if self.head_dim is None and d_model % self.num_heads:
            raise ValueError(f"d_model {d_model} not divisible by {self.num_heads} heads")
        d_head = d_model // self.num_heads if self.head_dim is None else self.head_dim
        kv_heads = self.num_heads if self.num_kv_heads is None else self.num_kv_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"{self.num_heads} heads do not divide over {kv_heads} key-value heads")
        dense = lambda name, heads=self.num_heads, width=d_head: nn.DenseGeneral(  # noqa: E731
            (heads, width), axis=-1, use_bias=False, dtype=self.dtype, name=name,
            dot_general=_dot_general(self.accum_dtype), **_given(self.matrix_init),
        )
        norm_over = None if self.qk_norm_eps is None else "head" if self.qk_norm_over == "head" else "row"
        norm_kind = self.norm if norm_over == "head" else "rmsnorm"
        count = telemetry.get_registry().add
        # queries and keys from their projections to the flash kernels in one pass each (``pallas_qk_prep``), where the
        # call's own shapes and backend admit it; XLA's float32 passes, a cast and the kernels' transposes otherwise
        fused = pallas_qk_prep.takes_kernel(
            self.attn_impl, self.comm, d_head, norm_over, norm_kind, self.rope_theta is not None
        )
        if self.gate:
            with jax.named_scope("attn.gate"):
                q = dense("query", width=2 * d_head)(x)  # a head's query beside its gate
                if fused:
                    g = q[..., d_head:]  # the pass reads the queries' lanes of the projection as it stands
                else:
                    q, g = jnp.split(q, 2, axis=-1)
        else:
            q = dense("query")(x)
        k, v = dense("key", kv_heads)(x), dense("value", kv_heads)(x)
        if fused:
            prep = pallas_qk_prep.Pass(
                d_head, norm_over, self.qk_norm_eps, self.rope_theta, self.rotary_fraction, self.dtype,
                pallas_qk_prep.ROWS, jax.default_backend() != "tpu",
            )

            def gain(name, heads):
                """``<name>/scale`` as the norm's own module holds it (over a head ``(d,)``, over the row
                ``(heads, d)``), as rows of ``d`` for the kernels; nothing without a norm."""
                if norm_over is None:
                    return None
                shape = (d_head,) if norm_over == "head" else (heads, d_head)
                return _NormGain(norm_kind, name=name)(shape).reshape(-1, d_head)

            with jax.named_scope("attn.qk_prep"):
                q = pallas_qk_prep.qk_prep(q, gain("q_norm", self.num_heads), prep)
                k = pallas_qk_prep.qk_prep(k, gain("k_norm", kv_heads), prep)
            count("attn.qk_prep.kernel", 2)
        else:
            if norm_over == "head":
                q = _norm(self.norm, self.qk_norm_eps, jnp.float32, "q_norm")(q)
                k = _norm(self.norm, self.qk_norm_eps, jnp.float32, "k_norm")(k)
            elif norm_over == "row":
                over_heads = dict(reduction_axes=(-2, -1), feature_axes=(-2, -1))
                q = _norm("rmsnorm", self.qk_norm_eps, jnp.float32, "q_norm", **over_heads)(q)
                k = _norm("rmsnorm", self.qk_norm_eps, jnp.float32, "k_norm", **over_heads)(k)
            if self.rope_theta is not None:
                q = rotary(q, self.rope_theta, self.rotary_fraction)
                k = rotary(k, self.rope_theta, self.rotary_fraction)
            if norm_over is not None or self.rope_theta is not None:
                count("attn.qk_prep.xla", 2)
            q, k = q.astype(self.dtype), k.astype(self.dtype)
        o = _attend(
            q, k, v.astype(self.dtype), impl=self.attn_impl, causal=self.causal, comm=self.comm,
            flash_bwd_impl=self.flash_bwd_impl,
            block_size=self.block_size, window=self.window, head_major=fused,
        )
        if self.gate:
            with jax.named_scope("attn.gate"):
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))).astype(self.dtype)
        return nn.DenseGeneral(
            d_model, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="out",
            dot_general=_dot_general(self.accum_dtype),
            **_given(self.matrix_init if self.out_init is None else self.out_init),
        )(o)


class Latent(NamedTuple):
    """The five sizes of a latent attention (``mixer="latent"``): the ranks of
    the queries' and of the keys' and values' latents, a head's part without
    positions, its rotary part, and a value head's size."""

    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int


class LatentAttention(nn.Module):
    """Multi-head latent attention as it is trained (DeepSeek-V2/V3's MLA, HF
    ``modeling_deepseek_v3.py``; no bias anywhere), ``(B, T, D_model)`` in and
    out, with ``n`` an RMSNorm over a latent::

        c_q = n(u W_qa);   [q_nope | q_rope] = c_q W_qb          a head: nope | rope
        [c_kv | k_r] = u W_kva;   c_kv = n(c_kv);   [k_nope | v] = c_kv W_kvb
        q_rope, k_r = rotary(q_rope), rotary(k_r)                rotate-half over ``rope``; k_r has no head axis
        q_i = [q_nope,i | q_rope,i];   k_i = [k_nope,i | k_r]    the same k_r for every head i
        out = [softmax(q_i k_i^T / sqrt(nope + rope)) v_i]_i W_o

    The latent is not absorbed into the queries: the core is plain multi-head
    attention over the assembled rows, the same :func:`_attend` call as
    :class:`MultiHeadAttention`'s, so the flash kernels, their kept residuals
    and the scopes ``attn.*`` are shared. A head's query and key must be as
    wide as its value (``nope + rope == v``). The two norms and rotary are
    float32; the five projections take ``dtype`` operands and give
    ``accum_dtype`` results. Scopes: ``mla.down`` (``W_qa``, ``W_kva``, the two
    norms), ``mla.up`` (``W_qb``, ``W_kvb``), ``mla.assemble`` (rotary, the
    broadcast of ``k_r`` over the heads and the joins; backward the splits and
    the sum over the heads). Counters, once a trace: ``mla.mixers`` and
    ``mla.key_rows_built`` (the ``B x T x heads x (nope + rope)`` elements the
    joined keys take: what a kernel that took ``k_r`` as an operand of its own
    would not write)."""

    num_heads: int
    latent: Latent
    attn_impl: str = "local"
    causal: bool = True
    comm: Optional[Any] = None
    block_size: Optional[int] = None
    dtype: Any = jnp.float32
    flash_bwd_impl: str = "auto"
    norm_eps: float = 1e-6
    rope_theta: Optional[float] = None  # None: no positions
    accum_dtype: Optional[Any] = None
    matrix_init: Any = None
    out_init: Any = None

    @nn.compact
    def __call__(self, u):
        q_rank, kv_rank, nope, rope, v_dim = self.latent
        if nope + rope != v_dim:
            raise ValueError(
                f"a latent head's query and key ({nope} + {rope}) must be as wide as its value ({v_dim})"
            )
        b, t, d_model = u.shape
        heads = self.num_heads
        dense = lambda features, name: nn.DenseGeneral(  # noqa: E731
            features, axis=-1, use_bias=False, dtype=self.dtype, name=name,
            dot_general=_dot_general(self.accum_dtype), **_given(self.matrix_init),
        )
        count = telemetry.get_registry().add
        count("mla.mixers")
        count("mla.key_rows_built", b * t * heads * (nope + rope))
        with jax.named_scope("mla.down"):
            c_q = _norm("rmsnorm", self.norm_eps, jnp.float32, "q_a_norm")(dense(q_rank, "q_a")(u))
            c_kv, k_r = jnp.split(dense(kv_rank + rope, "kv_a")(u), [kv_rank], axis=-1)
            c_kv = _norm("rmsnorm", self.norm_eps, jnp.float32, "kv_a_norm")(c_kv)
        with jax.named_scope("mla.up"):
            q = dense((heads, nope + rope), "q_b")(c_q)
            kv = dense((heads, nope + v_dim), "kv_b")(c_kv)
        with jax.named_scope("mla.assemble"):
            q_rope, k_r = q[..., nope:], k_r[:, :, None, :]  # one key of ``rope`` a position
            if self.rope_theta is not None:
                q_rope, k_r = rotary(q_rope, self.rope_theta), rotary(k_r, self.rope_theta)
            q = jnp.concatenate([q[..., :nope].astype(self.dtype), q_rope.astype(self.dtype)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope].astype(self.dtype), jnp.broadcast_to(k_r.astype(self.dtype), (b, t, heads, rope))],
                axis=-1,
            )
            v = kv[..., nope:].astype(self.dtype)
        o = _attend(
            q, k, v, impl=self.attn_impl, causal=self.causal, comm=self.comm,
            flash_bwd_impl=self.flash_bwd_impl, block_size=self.block_size,
        )
        return nn.DenseGeneral(
            d_model, axis=(-2, -1), use_bias=False, dtype=self.dtype, name="out",
            dot_general=_dot_general(self.accum_dtype),
            **_given(self.matrix_init if self.out_init is None else self.out_init),
        )(o)


class GatedShortConv(nn.Module):
    """The gated short convolution, the mixer of the LFM2 family (HF
    ``modeling_lfm2_moe.py``), ``(B, T, D_model)`` in and out::

        [b | c | x] = u W_in                 W_in: D -> 3 D, split in thirds in that order
        y_t = sum_j w[:, j] * (b * x)_{t - (K - 1) + j}      depthwise, causal, no bias, no activation
        out = (c * y) W_out

    No state beyond the ``K - 1`` earlier positions, no positions. The two
    gates and the taps are float32 (:func:`heat_tpu.nn.deltanet.gated_short_conv`,
    whose backward pass keeps ``b``, ``c``, ``x`` and ``w`` alone); the two
    projections take ``dtype`` operands and give ``accum_dtype`` results, under
    the scope ``conv.project``; the gates and the taps run under ``conv.mix``.
    The counter ``conv.mixers`` counts the mixers a trace builds. The taps are
    drawn uniform in ``+-1 / sqrt(K)`` (torch's draw for a depthwise ``Conv1d``
    of ``K`` taps: a convolution that passes its input on at its own size)."""

    taps: int = 3
    dtype: Any = jnp.float32
    accum_dtype: Optional[Any] = None
    matrix_init: Any = None  # None: lecun_normal
    out_init: Any = None  # None: as matrix_init

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        out_dtype = self.dtype if self.accum_dtype is None else self.accum_dtype
        init = nn.initializers.lecun_normal() if self.matrix_init is None else self.matrix_init
        w_in = self.param("in_proj", init, (d, 3 * d), jnp.float32)
        w = self.param(
            "conv", nn.initializers.variance_scaling(1 / 3, "fan_in", "uniform", in_axis=1, out_axis=0),
            (d, self.taps), jnp.float32,
        )
        w_out = self.param("out_proj", init if self.out_init is None else self.out_init, (d, d), jnp.float32)
        telemetry.get_registry().add("conv.mixers")

        def project(a, m):
            return jnp.dot(a.astype(self.dtype), m.astype(self.dtype), preferred_element_type=out_dtype)

        with jax.named_scope("conv.project"):
            # one matrix, read in three column ranges: no (tokens, 3 D) result to slice
            b, c, x = (project(u, w_in[:, i * d:(i + 1) * d]) for i in range(3))
        with jax.named_scope("conv.mix"):
            y = gated_short_conv(b, c, x, w)
        with jax.named_scope("conv.project"):
            return project(y, w_out)


MIXERS = ("attention", "deltanet", "shortconv", "latent")


class TransformerBlock(nn.Module):
    """Pre-norm residual block: x + mixer(norm(x)); x + ffn(norm(x)), the
    mixer attention, the Gated DeltaNet, the gated short convolution or the
    latent attention, the feed-forward a SwiGLU MLP or the dropless expert layer."""

    num_heads: int
    mlp_ratio: float = 4.0
    attn_impl: str = "local"
    causal: bool = True
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    dtype: Any = jnp.float32
    flash_bwd_impl: str = "auto"
    norm: str = "layernorm"
    norm_eps: Optional[float] = None  # None = flax's default (1e-6)
    qk_norm: Union[bool, str] = False  # True: over all of hidden; "head": over each head
    rope_theta: Optional[float] = None
    ffn: str = "swiglu"
    d_ff: Optional[int] = None  # None = d_model * mlp_ratio; one expert's width for "moe"
    num_experts: int = 0
    experts_per_token: int = 0
    accum_dtype: Optional[Any] = None
    mixer: str = "attention"  # one of MIXERS
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rotary_fraction: float = 1.0
    attn_gate: bool = False
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    norm_topk: bool = False
    shared_d_ff: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    init_std: Optional[float] = None  # None: each layer's own default
    out_init_std: Optional[float] = None  # the matrices that write into the residual stream
    window: Optional[int] = None
    sandwich_norm: bool = False  # x + norm(mixer(norm(x))); x + norm(ffn(norm(x)))
    router_score: str = "softmax"
    route_scale: float = 1.0
    router_bias: bool = False
    shared_gate: bool = True
    conv_taps: int = 3
    norm_topk_eps: float = 1e-20
    held_window: float = 2.0
    latent: Optional[Latent] = None  # the sizes of a "latent" mixer

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        stream = self.dtype if self.accum_dtype is None else self.accum_dtype
        eps = 1e-6 if self.norm_eps is None else self.norm_eps
        matrix = _matrix_init(self.init_std)
        out = _matrix_init(self.init_std if self.out_init_std is None else self.out_init_std)
        # the norm on a branch's output, where the block has four
        after = lambda name, y: _norm(self.norm, self.norm_eps, stream, name)(y) if self.sandwich_norm else y  # noqa: E731
        h = _norm(self.norm, self.norm_eps, stream, "ln1")(x)
        if self.mixer == "deltanet":
            x = x + after("ln1_post", GatedDeltaNet(
                self.gdn_key_heads, self.gdn_value_heads, self.gdn_key_dim, self.gdn_value_dim,
                self.gdn_conv, eps, self.dtype, self.accum_dtype, matrix_init=matrix, out_init=out, name="gdn",
            )(h))
        elif self.mixer == "attention":
            x = x + after("ln1_post", MultiHeadAttention(
                self.num_heads, self.attn_impl, self.causal, self.comm,
                self.block_size, self.dtype, self.flash_bwd_impl,
                eps if self.qk_norm else None, self.rope_theta, self.accum_dtype,
                self.num_kv_heads, self.head_dim,
                self.qk_norm if isinstance(self.qk_norm, str) else None,
                self.norm, self.rotary_fraction, self.attn_gate, matrix, out, self.window,
                name="attn",
            )(h))
        elif self.mixer == "shortconv":
            x = x + after("ln1_post", GatedShortConv(
                self.conv_taps, self.dtype, self.accum_dtype, matrix, out, name="conv",
            )(h))
        elif self.mixer == "latent":
            x = x + after("ln1_post", LatentAttention(
                self.num_heads, Latent(*self.latent), self.attn_impl, self.causal, self.comm, self.block_size,
                self.dtype, self.flash_bwd_impl, eps, self.rope_theta, self.accum_dtype, matrix, out, name="attn",
            )(h))
        else:
            raise ValueError(f"mixer must be one of {MIXERS}, got {self.mixer!r}")
        h = _norm(self.norm, self.norm_eps, stream, "ln2")(x)
        d_ff = int(d_model * self.mlp_ratio) if self.d_ff is None else self.d_ff
        if self.ffn == "moe":
            return x + after("ln2_post", DroplessMoE(
                self.num_experts, self.experts_per_token, d_ff,
                dtype=self.dtype, accum_dtype=self.accum_dtype,
                norm_topk=self.norm_topk, shared_d_ff=self.shared_d_ff,
                experts_held=self.experts_held, matrix_init=matrix, out_init=out,
                score=self.router_score, route_scale=self.route_scale,
                shared_gate=self.shared_gate, select_bias=self.router_bias,
                norm_topk_eps=self.norm_topk_eps, held_window=self.held_window, name="moe",
            )(h))
        if self.ffn != "swiglu":
            raise ValueError(f"ffn must be 'swiglu' or 'moe', got {self.ffn!r}")
        dense = lambda width, name, init: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name,
            dot_general=_dot_general(self.accum_dtype), **_given(init),
        )
        h = nn.silu(dense(d_ff, "gate", matrix)(h)) * dense(d_ff, "up", matrix)(h)  # SwiGLU
        return x + after("ln2_post", dense(d_model, "down", out)(h))


class TransformerLM(nn.Module):
    """Causal LM: token embedding → blocks → final LN → logits, by a head of
    its own (``lm_head``) or, with ``tie_embeddings``, by the embedding table
    itself (``params`` then holds no ``lm_head``)."""

    vocab_size: int
    d_model: int
    num_heads: int
    num_layers: int
    max_len: int = 2048
    mlp_ratio: float = 4.0
    attn_impl: str = "local"
    comm: Optional[Any] = None
    block_size: Optional[int] = None  # None = each impl's tuned default
    # checkpoint each block: O(L) -> O(1) activations. A block keeps what its
    # flash kernels name (`pallas_attention.KEPT_RESIDUALS`: the attention
    # core's output and its log-sum-exp, one float a row; (2·head_dim + 4)
    # bytes a position and query head: 136 MB a block at 16,384 positions x 32
    # heads of 128) and the backward pass recomputes everything else in the
    # block, so a flash forward kernel runs once a step. The other attention
    # forms (local, ring, ulysses) name nothing and are recomputed whole
    remat: bool = False
    # None = recompute all but the above; "dots" = also save MXU dot outputs
    # and recompute only the cheap elementwise ops (jax.checkpoint_policies.
    # dots_with_no_batch_dims_saveable) — usually faster when HBM allows
    remat_policy: Optional[str] = None
    dtype: Any = jnp.float32
    flash_bwd_impl: str = "auto"
    # the architecture (see the module docstring); defaults = the model above
    norm: str = "layernorm"
    norm_eps: Optional[float] = None
    positions: str = "learned"  # or "rope": no position table
    rope_theta: float = 10000.0
    qk_norm: bool = False
    ffn: str = "swiglu"
    d_ff: Optional[int] = None
    num_experts: int = 0
    experts_per_token: int = 0
    # results of matrix products, the residual stream and the norms keep
    # this dtype while the products take ``dtype`` operands; None = dtype
    accum_dtype: Optional[Any] = None
    # one period of the layer pattern: block i takes mixers[i % len(mixers)]
    mixers: Tuple[str, ...] = ("attention",)
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rotary_fraction: float = 1.0
    attn_gate: bool = False
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    norm_topk: bool = False
    shared_d_ff: int = 0
    experts_held: Optional[Tuple[int, int]] = None  # (first, count): this chip's share of every expert layer
    init_std: Optional[float] = None
    out_init_std: Optional[float] = None
    # beside ``mixers``, one period each: the sliding window of block i's
    # attention (None: full) and whether it rotates q and k (positions="rope")
    windows: Tuple[Optional[int], ...] = (None,)
    rotary: Tuple[bool, ...] = (True,)
    sandwich_norm: bool = False
    embed_scale: Optional[float] = None  # h_0 = E[token] * embed_scale
    dense_layers: int = 0  # leading blocks with a SwiGLU of width dense_d_ff in place of ``ffn``
    dense_d_ff: Optional[int] = None
    router_score: str = "softmax"
    route_scale: float = 1.0
    router_bias: bool = False
    shared_gate: bool = True
    conv_taps: int = 3  # the taps of a "shortconv" mixer
    norm_topk_eps: float = 1e-20  # a sigmoid router's top-k weights are divided by their sum + this
    tie_embeddings: bool = False  # logits = h E^T: the head is the embedding table
    held_window: float = 2.0  # with ``experts_held``: the first window of held rows, in even shares (DroplessMoE)
    latent: Optional[Latent] = None  # the sizes of the "latent" mixers (LatentAttention)
    # multi-token-prediction modules behind the trunk (DeepSeek-V3, section 2.2): module j merges the stream
    # before it with the embedding of token i + j + 1, runs one more block (``block<num_layers + j>``, the
    # pattern continued) and reads the trunk's head behind a norm of its own: ``__call__(..., mtp=True)``
    mtp_modules: int = 0
    # a looped stack (Ouro, arXiv:2510.25741): above 1, the ``num_layers`` blocks and ``ln_f`` run ``passes`` times on
    # one set of weights, pass t + 1 reading pass t's ``ln_f``; every pass's ``ln_f`` output is an exit's hidden state,
    # and one exit gate (``Linear(d_model, 1)`` with bias) is read after each pass but the last: with ``head=False``
    # the call gives ``(hidden (passes, B, T, D), gate logits (passes - 1, B, T))``, what :func:`causal_lm_loss`
    # weighs the exits by
    passes: int = 1

    def mixer_of(self, i: int) -> str:
        return self.mixers[i % len(self.mixers)]

    def window_of(self, i: int) -> Optional[int]:
        return self.windows[i % len(self.windows)]

    def rotates(self, i: int) -> bool:
        return self.positions == "rope" and self.rotary[i % len(self.rotary)]

    def expert_layers(self) -> Tuple[int, ...]:
        """The blocks whose feed-forward is the expert layer, the
        prediction modules' blocks among them."""
        return tuple(range(self.dense_layers, self.num_layers + self.mtp_modules)) if self.ffn == "moe" else ()

    @nn.compact
    def __call__(self, tokens, head: bool = True, mtp: bool = False):
        """Logits ``(B, T, vocab)``; with ``head=False`` the final norm's
        output ``(B, T, d_model)``, for a loss that applies ``lm_head``
        itself (:func:`causal_lm_loss`). With ``mtp`` a pair: that, and the
        same of each prediction module, whose position ``i`` stands for token
        ``i + j + 2`` (module ``j``; the embeddings it merges are rolled, so its
        last ``j + 1`` positions are fed the sequence's first tokens and are no
        prediction of anything: a loss weighs them zero, and attention being
        causal they reach no earlier position)."""
        if self.positions not in ("learned", "rope"):
            raise ValueError(f"positions must be 'learned' or 'rope', got {self.positions!r}")
        stream = self.dtype if self.accum_dtype is None else self.accum_dtype
        if tokens.shape[-1] > self.max_len:
            # nn.Embed's gather would silently clamp positions past the
            # table instead of erroring
            raise ValueError(
                f"sequence length {tokens.shape[-1]} exceeds max_len {self.max_len}"
            )
        embed = nn.Embed(
            self.vocab_size, self.d_model, dtype=stream, name="embed",
            **_given(_matrix_init(self.init_std), "embedding_init"),
        )
        x = embed(tokens)
        if self.embed_scale is not None:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        if self.positions == "learned":
            pos = nn.Embed(self.max_len, self.d_model, dtype=stream, name="pos")(
                jnp.arange(tokens.shape[-1])
            )
            x = x + pos[None]
        # rematerialization trades backward-pass FLOPs for activation
        # memory — the standard long-context recipe (HBM is the bottleneck).
        # A block's backward pass runs the block forward again, all but the
        # flash forward kernel: its output and log-sum-exp are kept by name
        block_cls = TransformerBlock
        if self.remat:
            keep = jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS)
            if self.remat_policy == "dots":
                keep = jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable, keep
                )
            block_cls = nn.remat(TransformerBlock, policy=keep)
            telemetry.get_registry().add("attn.kept", sum(
                self.attn_impl == "flash" and self.mixer_of(i) in ("attention", "latent")
                for i in range(self.num_layers + self.mtp_modules)
            ))

        def block(i, x):
            dense = i < self.dense_layers
            return block_cls(
                self.num_heads, self.mlp_ratio, self.attn_impl, True,
                self.comm, self.block_size, self.dtype,
                self.flash_bwd_impl, self.norm, self.norm_eps, self.qk_norm,
                self.rope_theta if self.rotates(i) else None,
                "swiglu" if dense else self.ffn, self.dense_d_ff if dense else self.d_ff,
                self.num_experts, self.experts_per_token,
                self.accum_dtype, self.mixer_of(i), self.num_kv_heads, self.head_dim,
                self.rotary_fraction, self.attn_gate, self.gdn_key_heads, self.gdn_value_heads,
                self.gdn_key_dim, self.gdn_value_dim, self.gdn_conv, self.norm_topk,
                self.shared_d_ff, self.experts_held, self.init_std, self.out_init_std,
                self.window_of(i), self.sandwich_norm, self.router_score, self.route_scale,
                self.router_bias, self.shared_gate, self.conv_taps, self.norm_topk_eps, self.held_window,
                self.latent, name=f"block{i}",
            )(x)

        gates = None
        if self.passes == 1:
            for i in range(self.num_layers):
                x = block(i, x)
            outs = [_norm(self.norm, self.norm_eps, stream, "ln_f")(x)]
        else:
            exits, gates = self._looped(block, x, stream)
            outs = [exits[-1]]
        if self.mtp_modules and (mtp or self.is_initializing()):
            telemetry.get_registry().add("lm.mtp.modules", self.mtp_modules)
            out_init = _matrix_init(self.init_std if self.out_init_std is None else self.out_init_std)
            for j in range(self.mtp_modules):
                with jax.named_scope("mtp.merge"):
                    ahead = embed(jnp.roll(tokens, -(j + 1), axis=-1))  # position i: token i + j + 1
                    if self.embed_scale is not None:
                        ahead = ahead * jnp.asarray(self.embed_scale, ahead.dtype)
                    x = nn.Dense(
                        self.d_model, use_bias=False, dtype=self.dtype, name=f"mtp{j}_eh_proj",
                        dot_general=_dot_general(self.accum_dtype), **_given(out_init),
                    )(jnp.concatenate([
                        _norm(self.norm, self.norm_eps, stream, f"mtp{j}_enorm")(ahead),
                        _norm(self.norm, self.norm_eps, stream, f"mtp{j}_hnorm")(x),
                    ], axis=-1))
                with jax.named_scope("mtp.block"):
                    x = block(self.num_layers + j, x)
                outs.append(_norm(self.norm, self.norm_eps, stream, f"mtp{j}_ln_f")(x))
        if self.tie_embeddings:
            def project(x):
                with jax.named_scope("lm.tied_head"):
                    return jnp.dot(
                        x.astype(self.dtype), embed.embedding.astype(self.dtype).T, preferred_element_type=stream,
                    )
        else:
            project = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head",
                dot_general=_dot_general(self.accum_dtype), **_given(_matrix_init(self.init_std)),
            )
            if not head and self.is_initializing():
                project(outs[0][:, :1])
        if head:
            outs = [project(x) for x in outs]
        elif gates is not None:
            return exits, gates
        return (outs[0], outs[1:]) if mtp else outs[0]

    def _looped(self, block, x, stream):
        """The stack run ``passes`` times on the same parameters as one loop
        (``nn.scan`` with the parameters broadcast: the compiled program holds
        each block's body once, and a shared weight's gradient is the loop's
        sum over its uses), under the scope ``lm.loop``: every pass's ``ln_f``
        output stacked ``(passes, B, T, D)``, and the exit gate's logits of all
        but the last ``(passes - 1, B, T)`` in float32 (one product a position,
        taken as a multiply and a sum so that no matrix unit rounds it), under
        ``lm.exit_gate``. Counter, once a trace: ``lm.loop.passes``."""
        if self.passes < 1:
            raise ValueError(f"passes={self.passes}: the stack runs once or more")
        if self.ffn == "moe" or self.mtp_modules:
            raise ValueError("a looped stack (passes > 1) takes SwiGLU blocks and no prediction module")
        telemetry.get_registry().add("lm.loop.passes", self.passes)

        def one_pass(mdl, x, _):
            for i in range(self.num_layers):
                x = block(i, x)
            h = _norm(self.norm, self.norm_eps, stream, "ln_f")(x)
            return h, h

        with jax.named_scope("lm.loop"):
            _, exits = nn.scan(
                one_pass, variable_broadcast="params", split_rngs={"params": False}, length=self.passes,
            )(self, x, None)
        w = self.param("exit_gate_kernel", nn.initializers.normal(0.02 if self.init_std is None else self.init_std),
                       (self.d_model, 1), jnp.float32)
        b = self.param("exit_gate_bias", nn.initializers.zeros, (1,), jnp.float32)
        with jax.named_scope("lm.exit_gate"):
            return exits, jnp.sum(exits[:-1].astype(jnp.float32) * w[:, 0], axis=-1) + b[0]


def olmoe_1b_7b(num_layers: int = 16, **fields) -> TransformerLM:
    """OLMoE-1B-7B (allenai, arXiv:2409.02060; config.json of
    ``OLMoE-1B-7B-0125-Instruct``) at its published widths: hidden 2048,
    16 heads of 128, 64 experts of width 1024, top-8 not renormalised,
    RMSNorm (eps 1e-5) before attention, before the experts and on the query
    and key projections, rotary positions (theta 10000), untied 50,304-row
    embedding and head, context 4,096. bfloat16 matmul operands, float32
    everything else. ``num_layers`` is the one size a chip forces down;
    ``fields`` passes what is not architecture (``attn_impl``, ``comm``,
    ``remat``, ...)."""
    arch = dict(
        vocab_size=50304, d_model=2048, num_heads=16, num_layers=num_layers,
        max_len=4096, norm="rmsnorm", norm_eps=1e-5, positions="rope",
        rope_theta=10000.0, qk_norm=True, ffn="moe", d_ff=1024, num_experts=64,
        experts_per_token=8, dtype=jnp.bfloat16, accum_dtype=jnp.float32,
        attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def qwen3_next_80b_a3b(
    num_layers: int = 48, experts_held: Optional[Tuple[int, int]] = None, vocab_size: int = 151936, **fields
) -> TransformerLM:
    """Qwen3-Next-80B-A3B (Qwen, 2025-09; ``config.json`` of
    ``Qwen3-Next-80B-A3B-Instruct``, equations of HF ``modeling_qwen3_next.py``)
    at its published widths: hidden 2048; blocks in periods of three Gated
    DeltaNet mixers (16 key and 32 value heads of 128, a causal depthwise
    convolution of 4 taps) and one gated attention (16 query heads on 2
    key-value heads of 256, a zero-centred RMSNorm on each query and key head,
    rotary positions on the first quarter of a head at theta 1e7, a sigmoid
    gate on the output); every block followed by 512 experts of width 512,
    the top 10 with their weights normalised, and a shared expert of width 512
    behind a sigmoid gate; zero-centred RMSNorm (eps 1e-6); untied 151,936-row
    embedding and head. The release's multi-token-prediction module is not
    part of ``config.json`` and is left out, as HF's model leaves it out
    (``mtp_modules=1`` among ``fields`` would add one in :func:`glm_4_7_flash`'s form).
    bfloat16 matmul operands, float32 everything else; matrices drawn at 0.02,
    those that write into the residual stream at ``0.02 / sqrt(2 * 48)``.

    ``num_layers``, ``experts_held`` (``(first, count)``: the experts of every
    layer that this chip holds, :class:`heat_tpu.nn.DroplessMoE`) and
    ``vocab_size`` (a slice of the vocabulary is a smaller vocabulary) are what
    one chip's share of a deployment sets; ``fields`` passes what is not
    architecture (``attn_impl``, ``comm``, ``remat``, ...)."""
    arch = dict(
        vocab_size=vocab_size, d_model=2048, num_heads=16, num_layers=num_layers,
        max_len=262144, norm="rmsnorm_zero", norm_eps=1e-6, positions="rope",
        rope_theta=1e7, rotary_fraction=0.25, qk_norm="head", num_kv_heads=2, head_dim=256,
        attn_gate=True, mixers=("deltanet", "deltanet", "deltanet", "attention"),
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128, gdn_conv=4,
        ffn="moe", d_ff=512, num_experts=512, experts_per_token=10, norm_topk=True,
        shared_d_ff=512, experts_held=experts_held, init_std=0.02,
        out_init_std=0.02 / math.sqrt(2 * 48),
        dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def trinity_mini(
    num_layers: int = 32, experts_held: Optional[Tuple[int, int]] = None, vocab_size: int = 200192, **fields
) -> TransformerLM:
    """Trinity-Mini (Arcee, 2025-12, 26B-A3B; ``config.json`` of
    ``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``, equations of HF
    ``modeling_afmoe.py``) at its published widths: hidden 2048, the embedding
    scaled by ``sqrt(2048)``; 32 query heads on 4 key-value heads of 128, a
    plain RMSNorm (eps 1e-5) on each query and key head and a sigmoid gate on
    the output; blocks in periods of three **sliding-window** layers (window
    2,048: a position sees itself and the 2,047 before it; rotary positions,
    theta 10,000) and one **full** layer without positions; four norms a block
    (before and after each branch); blocks 0 and 1 a SwiGLU of width 6,144,
    every later block 128 experts of width 1,024 chosen by ``sigmoid(x W_r) +
    b``, the top 8 weighted by their sigmoid over the eight's sum times 2.826,
    and an ungated shared expert of width 1,024; untied 200,192-row embedding
    and head. ``b`` is no parameter: the collection ``route_bias`` beside
    ``params``, moved after every step by :func:`heat_tpu.nn.balance_bias_rule`
    (rate 0.001) through ``make_train_step(state_rule=)``. bfloat16 matmul
    operands, float32 everything else; matrices drawn at 0.02, those that write
    into the residual stream at ``0.02 / sqrt(2 * 32)``.

    ``num_layers``, ``experts_held`` and ``vocab_size`` are what one chip's
    share of a deployment sets (as for :func:`qwen3_next_80b_a3b`); ``fields``
    passes what is not architecture (``attn_impl``, ``comm``, ``remat``, ...)."""
    arch = dict(
        vocab_size=vocab_size, d_model=2048, num_heads=32, num_layers=num_layers,
        max_len=131072, norm="rmsnorm", norm_eps=1e-5, positions="rope", rope_theta=10000.0,
        qk_norm="head", num_kv_heads=4, head_dim=128, attn_gate=True,
        windows=(2048, 2048, 2048, None), rotary=(True, True, True, False),
        sandwich_norm=True, embed_scale=math.sqrt(2048), dense_layers=2, dense_d_ff=6144,
        ffn="moe", d_ff=1024, num_experts=128, experts_per_token=8, norm_topk=True,
        router_score="sigmoid", route_scale=2.826, router_bias=True,
        shared_d_ff=1024, shared_gate=False, experts_held=experts_held,
        init_std=0.02, out_init_std=0.02 / math.sqrt(2 * 32),
        dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def lfm2_24b_a2b(
    num_layers: int = 40, experts_held: Optional[Tuple[int, int]] = None, vocab_size: int = 65536,
    first_block: int = 0, **fields
) -> TransformerLM:
    """LFM2-24B-A2B (Liquid AI; ``config.json`` of ``LiquidAI/LFM2-24B-A2B``,
    ``model_type`` ``lfm2_moe``, equations of HF ``modeling_lfm2_moe.py``) at its
    published widths: hidden 2048; blocks in periods of (conv, conv, attention,
    conv): the **gated short convolution** (:class:`GatedShortConv`, 3 taps, no
    bias) and grouped-query attention (32 query heads on 8 key-value heads of
    64, a plain RMSNorm, eps 1e-5, on each query and key head before rotary at
    theta 1e6, no gate, no window); two norms a block; blocks 0 and 1 a SwiGLU
    of width 11,776, every later block 64 experts of width 1,536 chosen by
    ``sigmoid(x W_r) + b``, the top 4 weighted by their sigmoid over the four's
    sum + 1e-6, no shared expert; the head tied to the 65,536-row embedding.
    ``b`` is the collection ``route_bias`` beside ``params``, moved after every
    step by :func:`heat_tpu.nn.balance_bias_rule` through
    ``make_train_step(state_rule=)``. bfloat16 matmul operands, float32
    everything else; matrices drawn at 0.02, those that write into the residual
    stream at ``0.02 / sqrt(2 * 40)``.

    ``num_layers``, ``experts_held`` and ``vocab_size`` are what one chip's
    share of a deployment sets (as for :func:`qwen3_next_80b_a3b`), and
    ``first_block``: the published block that this stage's first one is, which
    turns the mixers' period to start there and leaves ``2 - first_block``
    leading dense blocks. ``fields`` passes what is not architecture
    (``attn_impl``, ``comm``, ``remat``, ...)."""
    period = ("shortconv", "shortconv", "attention", "shortconv")
    arch = dict(
        vocab_size=vocab_size, d_model=2048, num_heads=32, num_layers=num_layers,
        max_len=128000, norm="rmsnorm", norm_eps=1e-5, positions="rope", rope_theta=1e6,
        qk_norm="head", num_kv_heads=8, head_dim=64,
        mixers=tuple(period[(first_block + i) % len(period)] for i in range(len(period))), conv_taps=3,
        dense_layers=max(0, 2 - first_block), dense_d_ff=11776,
        ffn="moe", d_ff=1536, num_experts=64, experts_per_token=4, norm_topk=True, norm_topk_eps=1e-6,
        router_score="sigmoid", router_bias=True, experts_held=experts_held, tie_embeddings=True,
        init_std=0.02, out_init_std=0.02 / math.sqrt(2 * 40),
        dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def glm_4_7_flash(
    num_layers: int = 47, experts_held: Optional[Tuple[int, int]] = None, vocab_size: int = 154880,
    mtp_modules: int = 1, **fields
) -> TransformerLM:
    """GLM-4.7-Flash (Z.ai, 30B-A3B; ``config.json`` of ``zai-org/GLM-4.7-Flash``,
    ``model_type`` ``glm4_moe_lite``, equations of HF ``modeling_deepseek_v3.py``,
    from which it derives) at its published widths: hidden 2048; every mixer a
    **latent attention** (:class:`LatentAttention`: 20 heads, queries through a
    latent of 768 and keys and values through one of 512, each with an RMSNorm
    in it, a head's query and key a part of 192 without positions beside a part
    of 64 with rotary positions at theta 1e6, **the rotary key one vector a
    position that all heads share**, values of 256); two norms a block (RMSNorm,
    eps 1e-5); block 0 a SwiGLU of width 10,240, every later block 64 experts
    of width 1,536 chosen by ``sigmoid(x W_r) + b``, the top 4 weighted by their
    sigmoid over the four's sum + 1e-20 times 1.8, and an ungated shared expert
    of width 1,536; untied 154,880-row embedding and head; and **one
    multi-token-prediction module** (``mtp_modules``; DeepSeek-V3's report,
    section 2.2: ``[norm(E[t_{i+1}]) | norm(h_i)] W_eh``, one more whole block,
    the trunk's head behind a norm of its own), whose loss
    :func:`causal_lm_loss` adds at ``mtp_coef``. ``b`` is the collection
    ``route_bias`` beside ``params``, moved after every step by
    :func:`heat_tpu.nn.balance_bias_rule` through ``make_train_step(state_rule=)``,
    the module's expert layer's among them. bfloat16 matmul operands, float32
    everything else; matrices drawn at 0.02, those that write into the residual
    stream (``W_eh`` among them) at ``0.02 / sqrt(2 * 47)``.

    ``num_layers``, ``experts_held`` and ``vocab_size`` are what one chip's
    share of a deployment sets (as for :func:`qwen3_next_80b_a3b`); ``fields``
    passes what is not architecture (``attn_impl``, ``comm``, ``remat``, ...)."""
    arch = dict(
        vocab_size=vocab_size, d_model=2048, num_heads=20, num_layers=num_layers,
        max_len=202752, norm="rmsnorm", norm_eps=1e-5, positions="rope", rope_theta=1e6,
        mixers=("latent",), latent=Latent(q_rank=768, kv_rank=512, nope=192, rope=64, v=256),
        dense_layers=1, dense_d_ff=10240,
        ffn="moe", d_ff=1536, num_experts=64, experts_per_token=4, norm_topk=True,
        router_score="sigmoid", route_scale=1.8, router_bias=True,
        shared_d_ff=1536, shared_gate=False, experts_held=experts_held, mtp_modules=mtp_modules,
        init_std=0.02, out_init_std=0.02 / math.sqrt(2 * 47),
        dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def ouro_2_6b(num_layers: int = 48, **fields) -> TransformerLM:
    """Ouro-2.6B (ByteDance; ``config.json`` of ``ByteDance/Ouro-2.6B``,
    ``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
    Models", arXiv:2510.25741) at its published widths: hidden 2048, 16 heads of
    128 on as many key-value heads, no bias and no query or key norm, rotary
    positions (rotate-half, theta 1e6), a SwiGLU of width 5,632, four RMSNorms a
    block (eps 1e-6, before **and after** each sublayer: ``sandwich_norm``), an
    untied 49,152-row embedding and head, and **the whole stack run four times
    on one set of weights** (``passes``, the config's ``total_ut_steps``): the
    final norm after every pass, whose output is that exit's hidden state and
    the next pass's input, and **one exit gate**
    (``sigmoid(h . w_g + b_g)`` after every pass but the last), by whose
    distribution :func:`causal_lm_loss` weighs the four exits' cross-entropies.
    ``model.apply(params, tokens)`` gives the last exit's logits (the config's
    ``early_exit_threshold`` 1: inference leaves at the last pass). bfloat16
    matmul operands, float32 everything else, the gate and the exit
    distribution among it; matrices drawn at 0.02, those that write into the
    residual stream at ``0.02 / sqrt(2 * 48 * 4)`` (every block writes four
    times), the gate's weights at 0.02 and its bias 0.

    ``num_layers`` is the one size a chip forces down (a pipeline stage's
    blocks); ``fields`` passes what is not architecture (``attn_impl``,
    ``comm``, ``remat``, ...)."""
    arch = dict(
        vocab_size=49152, d_model=2048, num_heads=16, num_layers=num_layers, max_len=65536,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1e6, d_ff=5632, sandwich_norm=True,
        passes=4, init_std=0.02, out_init_std=0.02 / math.sqrt(2 * 48 * 4),
        dtype=jnp.bfloat16, accum_dtype=jnp.float32, attn_impl="flash",
    )
    return TransformerLM(**{**arch, **fields})


def exit_distribution(gates):
    """The logarithm of the exit distribution ``(passes, ...)`` from the gate's
    logits ``(passes - 1, ...)``, float32: ``p_t = lam_t prod_{j<t} (1 -
    lam_j)``, ``p_last = prod_j (1 - lam_j)``, ``lam = sigmoid(gates)``, taken
    as sums of ``log_sigmoid`` so that no product underflows; ``exp`` of it
    sums to 1 over the passes."""
    gates = gates.astype(jnp.float32)
    none = jnp.zeros_like(gates[:1])
    stayed = jnp.concatenate([none, jnp.cumsum(jax.nn.log_sigmoid(-gates), axis=0)])  # sum_{j<t} log(1 - lam_j)
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(gates), none])


def read_exits(loss, aux):
    """:func:`~heat_tpu.nn.moe.read_routing` for a looped model's step: the
    loss and ``aux`` on the host in the one transfer, and the exit gate counted
    there: ``lm.exit.steps``, and ``lm.exit.expected_pass`` summed over them
    (divide by the steps)."""
    loss, aux = read_routing(loss, aux)
    reg = telemetry.get_registry()
    reg.add("lm.exit.steps", 1)
    reg.add("lm.exit.expected_pass", float(aux["expected_pass"]))
    return loss, aux


def causal_lm_loss(
    model: TransformerLM, *, load_balance_coef: float = 0.0, router_z_coef: float = 0.0, mtp_coef: float = 0.3,
    exit_beta: float = 0.05,
):
    """``loss_fn(params, tokens) -> (loss, aux)`` for ``make_train_step(...,
    has_aux=True)``: mean next-token cross-entropy over the ``T - 1`` targets
    of each row of ``tokens (B, T)``, taken over blocks of positions so that
    no ``(tokens, vocab)`` array is held and, under differentiation, with the
    head's gradients formed in the pass that forms its logits
    (:func:`blocked_cross_entropy`, which takes the mask and the divisor as
    its weights), plus
    ``load_balance_coef`` x the mean over the expert layers of their
    ``load_balance`` term and ``router_z_coef`` x that of ``router_z``.
    ``aux`` holds ``ce``, ``load_balance``, ``router_z``, ``expert_counts``
    (layers x experts), ``assignments_due`` (layers x tokens x top-k) and
    ``assignments_computed`` (those the grouped products computed with the
    chosen expert, :func:`heat_tpu.nn.moe.rows_computed`: a dropless routing
    gives ``assignments_due``); a model that holds a share of its experts
    (``experts_held``) is due the assignments on those, and gives the layers x
    tokens x top-k as ``assignments_routed`` and the rows its layers moved round
    the held experts as ``rows_moved``; a model without expert layers
    gives the three scalars only; a model whose routers carry a selection bias
    (``router_bias``: ``params`` then holds the collection ``route_bias``)
    also gives ``route_bias_max_abs``; a model with prediction modules
    (``mtp_modules``) adds ``mtp_coef`` x ``ce_mtp`` to the loss and gives
    ``ce_mtp``: the mean over the modules of module ``j``'s cross-entropy
    against the token ``j + 2`` ahead, averaged over the ``T - j - 2`` positions
    that have one, through the same head (whose gradient is the sum of its
    uses), and its expert layers stand after the trunk's in everything that
    goes by layer. A looped model (``passes`` above 1, :func:`ouro_2_6b`)
    is trained on the expectation of its exits' cross-entropies under the
    gate's own distribution less ``exit_beta`` x that distribution's entropy
    (stage I of arXiv:2510.25741: an ELBO under a uniform prior up to a
    constant)::

        loss = mean_i [ sum_t p_t(i) CE(h(t)_i W_head, x_{i+1}) - exit_beta H(p(i)) ]

    over the ``T - 1`` positions that have a next token, all ``passes`` exits
    through the one head as ``passes x N`` rows of one
    :func:`blocked_cross_entropy` loop whose weights are ``p_t(i)`` over the
    count (their cotangent, the cross-entropy a row, is what reaches the gate);
    ``aux`` then holds ``ce`` (the expectation), ``exit_entropy`` and
    ``expected_pass`` (the mean of ``sum_t t p_t``, passes counted from 1)
    beside the two zero scalars, and :func:`read_exits` reads it. ``nn.read_routing(loss, aux)`` brings both to
    the host and counts the routing."""

    def head_loss(params, hidden, tokens, ahead, scope):
        """The mean cross-entropy of ``hidden (B, T, D)`` through the head
        against the token ``ahead`` positions on, over the positions that
        have one; the head's loop under ``scope``."""
        b, t = tokens.shape
        with jax.named_scope("lm.targets"):
            targets = jnp.roll(tokens, -ahead, axis=1).reshape(b * t)
            mask = jnp.broadcast_to(jnp.arange(t) < t - ahead, (b, t)).reshape(b * t)
            weights = mask.astype(jnp.float32) / (b * (t - ahead))
        with jax.named_scope(scope):
            head = functools.partial(
                blocked_cross_entropy, hidden.reshape(b * t, -1), targets=targets, weights=weights, dtype=model.dtype
            )
            if model.tie_embeddings:
                # the table is the head: its gradient is the gather's rows plus the head's product
                telemetry.get_registry().add("lm.head.tied")
                with jax.named_scope("lm.tied_head"):
                    return head(kernel=params["params"]["embed"]["embedding"].T)
            return head(kernel=params["params"]["lm_head"]["kernel"])

    def exits_loss(params, tokens):
        with jax.named_scope("lm.body"):
            exits, gates = model.apply(params, tokens, head=False)
        b, t = tokens.shape
        passes = exits.shape[0]
        with jax.named_scope("lm.exit_gate"):
            log_p = exit_distribution(gates)
            p = jnp.exp(log_p)
            counted = (jnp.arange(t) < t - 1).astype(jnp.float32) / (b * (t - 1))  # the last position has no next token
            entropy = -jnp.sum(p * log_p * counted)
            expected_pass = jnp.sum(p * jnp.arange(1, passes + 1, dtype=jnp.float32)[:, None, None] * counted)
            weights = (p * counted).reshape(passes * b * t)
        with jax.named_scope("lm.targets"):
            targets = jnp.tile(jnp.roll(tokens, -1, axis=1).reshape(b * t), passes)
        with jax.named_scope("lm.head_loss"):
            p_ = params["params"]
            kernel = p_["embed"]["embedding"].T if model.tie_embeddings else p_["lm_head"]["kernel"]
            ce = blocked_cross_entropy(exits.reshape(passes * b * t, -1), kernel, targets, weights, dtype=model.dtype)
        with jax.named_scope("lm.loss"):
            zero = jnp.zeros((), jnp.float32)
            aux = {"ce": ce, "load_balance": zero, "router_z": zero, "exit_entropy": entropy,
                   "expected_pass": expected_pass}
            return ce - exit_beta * entropy, aux

    def loss_fn(params, tokens):
        if model.passes > 1:
            return exits_loss(params, tokens)
        with jax.named_scope("lm.body"):
            (hidden, ahead), state = model.apply(params, tokens, head=False, mtp=True, mutable=["aux"])
        b, t = tokens.shape
        ce = head_loss(params, hidden, tokens, 1, "lm.head_loss")
        of_modules = [head_loss(params, h, tokens, j + 2, "mtp.head_loss") for j, h in enumerate(ahead)]
        layers = [state["aux"][f"block{i}"]["moe"]["moe"][0] for i in model.expert_layers()]
        with jax.named_scope("lm.loss"):  # the terms beside the cross-entropy, their sum
            zero = jnp.zeros((), jnp.float32)
            aux = {"ce": ce, "load_balance": zero, "router_z": zero}
            if of_modules:
                aux["ce_mtp"] = sum(of_modules) / len(of_modules)
            if layers:
                aux["load_balance"] = jnp.mean(jnp.stack([a["load_balance"] for a in layers]))
                aux["router_z"] = jnp.mean(jnp.stack([a["router_z"] for a in layers]))
                aux["expert_counts"] = jnp.stack([a["expert_counts"] for a in layers])
                aux["assignments_due"] = len(layers) * b * t * model.experts_per_token
                if model.experts_held is not None:  # a share: due are those on the held experts
                    aux["assignments_routed"] = aux["assignments_due"]
                    aux["assignments_due"] = sum(a["held"] for a in layers)
                    aux["rows_moved"] = sum(a["moved"] for a in layers)
                aux["assignments_computed"] = sum(a["computed"] for a in layers)
                if "route_bias" in params:  # the selection biases a rule moves: how far they have gone
                    aux["route_bias_max_abs"] = jnp.max(jnp.abs(jnp.stack(jax.tree.leaves(params["route_bias"]))))
            loss = ce + load_balance_coef * aux["load_balance"] + router_z_coef * aux["router_z"]
            if of_modules:
                loss = loss + mtp_coef * aux["ce_mtp"]
        return loss, aux

    return loss_fn

