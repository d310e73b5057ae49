"""Pallas TPU flash-attention kernel.

The hot op of the long-context stack (:mod:`heat_tpu.parallel.attention`)
hand-tiled for the TPU memory hierarchy: Q/K/V stream HBM→VMEM in
(block_q, block_k) tiles, the online-softmax accumulators (m, l, acc) live
in VMEM scratch across the K-block grid axis, and the QKᵀ / PV products hit
the MXU with explicit ``preferred_element_type=float32``. The reference
framework has no attention code at all (SURVEY §2.5); this kernel is the
TPU-native capability its ring/Alltoall mechanisms exist to enable, and a
drop-in replacement for the XLA-fused :func:`local_attention` path.

Numerics: same f32 online softmax and padding/causal mask semantics as
:func:`heat_tpu.parallel.attention.local_attention`. For f32 inputs the two
paths agree to tight tolerance (asserted on CPU via the Pallas
interpreter); for bf16 inputs the MXU dots run in bf16 with f32
accumulation (and p rounds to bf16 before the PV product — standard flash
practice), so agreement is to bf16 tolerance, also asserted. The backward
rebuilds probabilities from the saved O and log-sum-exp residuals — O(T)
memory (no stored (T, T) matrix), every MXU dot in the input dtype — in
one of two strategies: the ``"fused"`` single-pass kernel, which visits a
block pair once and shares the rebuild across dq, dk and dv with a
key-value head's float32 dK and dV resident in VMEM, or the ``"two_pass"``
hand-tiled kernels (dq; dk/dv), which rebuild it twice. The shape decides
(``flash_attention(bwd_impl="auto")``, `_bwd_takes_fused`): fused wherever
its resident blocks fit the chip's VMEM.
The inference-only forward skips the log-sum-exp output entirely.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry

NEG_INF = -1e30
# the names `_flash_fwd` gives the attention's output and its log-sum-exp (one
# float a row): what `jax.checkpoint_policies.save_only_these_names` takes to
# rematerialise everything round the forward kernel and not the kernel
KEPT_RESIDUALS = ("attn.out", "attn.lse")
_LANES = 128  # TPU lane width: scratch rows are broadcast across it
# heat_tpu enables jax_enable_x64; a Python-int 0 in an index map then traces
# as an i64 constant, which Mosaic cannot legalize — pin index literals to i32
_I0 = np.int32(0)
# tiles of the windowed form (``flash_attention(window=)`` with no blocks given). TPU v5e, 32 query heads
# on 4 key-value heads of 128, 16,384 positions, window 2,048, bfloat16; forward / forward + dq + dk, dv in ms
# (my chip run, PR 32, call 2): 1024 x 1024 **9.30 / 27.64**; 512 x 1024 10.01 / 28.71; 256 x 1024 11.11 / 31.23;
# 512 x 512 13.44 / 29.69; 1024 x 512 14.18 / 34.49; 256 x 512 14.72 / 35.13; 2048 x 512 15.86 / 40.23;
# 512 x 256 24.34 / 47.65; 256 x 256 24.25 / 53.82; 1024 x 256 25.67 / 48.74 (the full causal form at its own
# 512 x 1024: 26.91 / 88.99). Wide key blocks win although a band of three of them (3,072 keys for the 2,048 a
# query sees) wastes more of the edge blocks than five of 512 do: a grid step's fixed cost outweighs it.
_WINDOW_BLOCK_Q = 1024
_WINDOW_BLOCK_K = 1024


def _tiles(window, block_q, block_k):
    """Block sizes not given: the tuned tiles of the form."""
    tuned = (512, 1024) if window is None else (_WINDOW_BLOCK_Q, _WINDOW_BLOCK_K)
    return tuned[0] if block_q is None else block_q, tuned[1] if block_k is None else block_k


class _Band:
    """The static geometry of a causal grid: position ``t`` sees keys ``t -
    window < j <= t``, so a block of one axis meets a band of blocks of the
    other, and the grid's sequential axis runs over that band alone (``steps``
    blocks at most), its block index a function of the other axis's. No window
    is one that sees every key (``t_q + t_k``): the band ends at the diagonal.

    ``keys``: for query block ``i`` the key blocks ``lo(i) .. hi(i)``;
    otherwise for key block ``i`` the query blocks ``lo(i) .. hi(i)``. ``rows``
    and ``cols`` are the block sizes of the axis given and of the band's axis,
    ``n`` how many blocks the band's axis has. ``lo`` and ``hi`` take int32
    tracers (index maps and kernel bodies: literals pinned to int32); a step
    past ``hi`` reads block ``hi`` again (no new DMA) and computes nothing.
    ``visited``, ``streamed`` and ``live`` count, over the rows, the grid's
    steps, those that name another block than the step before (a row's first
    among them: what is copied) and those whose block holds a visible pair."""

    def __init__(self, window, rows, cols, n, n_rows, keys):
        self.window, self.rows, self.cols, self.n, self.keys = window, rows, cols, n, keys
        spans = [self._span(i) for i in range(n_rows)]
        self.steps = max(1, max(hi - lo + 1 for lo, hi in spans))
        self.live = sum(max(0, hi - lo + 1) for lo, hi in spans)
        self.visited = self.steps * n_rows
        self.streamed = sum(len({min(lo + step, hi) for step in range(self.steps)}) for lo, hi in spans)

    def _span(self, i):  # Python ints
        if self.keys:
            return max(i * self.rows - (self.window - 1), 0) // self.cols, min((i * self.rows + self.rows - 1) // self.cols, self.n - 1)
        return min(i * self.rows // self.cols, self.n - 1), min((i * self.rows + self.rows + self.window - 2) // self.cols, self.n - 1)

    def lo(self, i):
        rows, cols = np.int32(self.rows), np.int32(self.cols)
        if self.keys:
            return jax.lax.div(jnp.maximum(i * rows - np.int32(self.window - 1), _I0), cols)
        return jnp.minimum(jax.lax.div(i * rows, cols), np.int32(self.n - 1))

    def hi(self, i):
        rows, cols = np.int32(self.rows), np.int32(self.cols)
        reach = self.rows - 1 if self.keys else self.rows + self.window - 2
        return jnp.minimum(jax.lax.div(i * rows + np.int32(reach), cols), np.int32(self.n - 1))

    def block(self, i, step):
        """The band's block that grid step ``step`` of row-block ``i`` reads."""
        return jnp.minimum(self.lo(i) + step, self.hi(i))


def _visible(iq, ik, *, causal, kv_valid, block_q, block_k, window):
    """The (bq, bk) mask of the pairs a score block may keep: keys before
    ``kv_valid``, not after the query (``causal``) and, with a ``window``,
    fewer than ``window`` positions before it."""
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_valid
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - np.int32(window))
    return mask


def _inside_band(iq, ik, *, kv_valid, block_q, block_k, window):
    """Whether every pair of score block (iq, ik) is visible: such a block
    takes no mask."""
    first_q, first_k = iq * block_q, ik * block_k
    inside = first_k + (block_k - 1) <= first_q
    if window is not None:
        inside = inside & (first_k > first_q + (block_q - 1 - window))
    return inside & (first_k + block_k <= kv_valid)


def _when_live(live, iq, ik, accumulate, *, causal, kv_valid, block_q, block_k, window):
    """Run ``accumulate(masked)`` where the block is live: in the causal
    forms, the blocks wholly inside the band without their mask."""
    if not causal:
        pl.when(live)(functools.partial(accumulate, True))
        return
    inside = _inside_band(
        iq, ik, kv_valid=kv_valid, block_q=block_q, block_k=block_k, window=window
    )
    pl.when(live & inside)(functools.partial(accumulate, False))
    pl.when(live & jnp.logical_not(inside))(functools.partial(accumulate, True))


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
    *, scale, causal, kv_valid, block_q, block_k, window=None, band=None,
):
    """Grid = (B, H, num_q_blocks, num_k_blocks); last axis is sequential.

    Refs arrive as (1, 1, block, D) VMEM tiles. The (m, l, acc) scratch
    persists across the K axis — initialised at ik == 0, finalised into
    ``o_ref`` at the last K block. With ``causal`` the last axis runs over
    the steps of ``band`` (:class:`_Band`) and the key block follows from it.
    """
    iq = pl.program_id(2)
    ik = step = pl.program_id(3)
    nk = pl.num_programs(3)
    # Mosaic legalizes only f32 float constants — keep every scalar f32
    neg_inf = jnp.float32(NEG_INF)
    half_neg = jnp.float32(NEG_INF / 2)

    @pl.when(step == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, neg_inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # causal: the steps walk the band's key blocks, the diagonal's the last; a
    # step past it names that block again, so nothing is copied for it, and
    # computes nothing
    if causal:
        ik = band.lo(iq) + step
        live = ik <= band.hi(iq)
    else:
        live = ik >= 0  # always true, keeps one code path

    def _accumulate(masked):
        # MXU dots run in the INPUT dtype with f32 accumulation
        # (preferred_element_type): bf16 inputs hit the full-rate bf16 MXU
        # (an up-front astype(f32) would force true-f32 passes at ~1/4 the
        # throughput); f32 inputs keep exact f32 passes. Softmax stays f32.
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * jnp.float32(scale)  # (bq, bk), f32

        if masked:
            mask = _visible(
                iq, ik, causal=causal, kv_valid=kv_valid, block_q=block_q,
                block_k=block_k, window=window,
            )
            s = jnp.where(mask, s, neg_inf)

        m_prev = m_s[:, 0:1]  # (bq, 1), lanes hold copies
        l_prev = l_s[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        zero = jnp.float32(0.0)
        m_safe = jnp.where(m_new <= half_neg, zero, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), zero) if masked else jnp.exp(s - m_safe)
        alpha = jnp.where(m_prev <= half_neg, zero, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # PV in v's dtype (standard flash practice): for bf16 v the f32
        # probabilities round to bf16 on the way into the MXU, accumulating
        # in f32 — covered by the bf16 agreement tolerance; f32 v unchanged
        p_mx = p if v.dtype == jnp.float32 else p.astype(v.dtype)
        pv = jax.lax.dot_general(
            p_mx, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, D), f32

        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)
        acc_s[:] = acc_s[:] * alpha + pv

    _when_live(
        live, iq, ik, _accumulate, causal=causal, kv_valid=kv_valid, block_q=block_q,
        block_k=block_k, window=window,
    )

    @pl.when(step == nk - 1)
    def _finalize():
        l_fin = l_s[:, 0:1]
        denom = jnp.where(l_fin == jnp.float32(0.0), jnp.float32(1.0), l_fin)
        o_ref[0, 0] = (acc_s[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp for the backward pass, lane-broadcast layout
            # (block_q, 128) like the scratch; fully-masked rows get +BIG so
            # the backward's exp(s - lse) is exactly 0 there
            big = jnp.float32(1e30)
            m_fin = m_s[:]
            l_full = l_s[:]
            m_fin_safe = jnp.where(m_fin <= half_neg, jnp.float32(0.0), m_fin)
            lse = jnp.where(
                l_full == jnp.float32(0.0),
                big,
                m_fin_safe + jnp.log(jnp.maximum(l_full, jnp.float32(1e-38))),
            )
            lse_ref[0, 0] = lse


def _kv_head(hi, group):
    """The key-value head that serves query head ``hi``: every ``group``
    consecutive query heads read one (``group`` 1: the head itself)."""
    return hi if group == 1 else jax.lax.div(hi, np.int32(group))


def _out_struct(shape, like, dtype=None):
    """ShapeDtypeStruct matching ``like``'s dtype (or an explicit one) —
    inside a shard_map the output must also declare how it varies over mesh
    axes (vma), inherited from the input block."""
    dtype = like.dtype if dtype is None else dtype
    try:
        vma = jax.typeof(like).vma
    except (AttributeError, TypeError):
        vma = None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _block_geometry(t_q, t_k, d, block_q, block_k):
    """Resolve the effective tiling: clamped blocks and pad amounts.

    The ONE source of truth for this arithmetic — `_pad_blocks` pads with
    it and `_flash_bwd_dispatch`'s "auto" sizes the fused dQ block with
    it, so the two can never disagree about the resident-block footprint.
    """
    block_q = min(block_q, -(-t_q // _LANES) * _LANES)
    block_k = min(block_k, -(-t_k // _LANES) * _LANES)
    pq = -t_q % block_q
    pk = -t_k % block_k
    pd = -d % _LANES
    return block_q, block_k, pq, pk, pd


def _pad_blocks(q, k, v, t_q, t_k, d, block_q, block_k):
    """Clamp blocks for short sequences, pad seq lengths to block multiples
    and the head dim to the lane width. Returns the padded operands and the
    resolved geometry."""
    block_q, block_k, pq, pk, pd = _block_geometry(
        t_q, t_k, d, block_q, block_k
    )
    # lanes added to the head dimension, a kernel call traced: every product of
    # the kernel then carries them as zeros (a head of 64 runs at half)
    telemetry.get_registry().add("attn.lanes_padded", pd)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if pd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pd)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pd)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pd)))
    return q, k, v, block_q, block_k, pq, pk, d + pd


def _key_band(causal, window, t_q, t_k, block_q, block_k):
    """The band of key blocks a query block meets (None unless ``causal``)."""
    if not causal:
        return None
    return _Band(window or t_q + t_k, block_q, block_k, t_k // block_k, t_q // block_q, keys=True)


def _flash_forward(
    q, k, v, scale, causal, kv_valid, block_q, block_k, interpret,
    return_lse=False, window=None,
):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    group = h // k.shape[1]
    # zero-pad K/V tails are masked out via kv_valid, Q tail rows sliced off
    q, k, v, block_q, block_k, pq, pk, dp = _pad_blocks(
        q, k, v, t_q, t_k, d, block_q, block_k
    )

    grid = (b, h, (t_q + pq) // block_q, (t_k + pk) // block_k)
    of_k = lambda bi, hi, qi, ki: (bi, _kv_head(hi, group), ki, _I0)  # noqa: E731
    band = _key_band(causal, window, t_q + pq, t_k + pk, block_q, block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, kv_valid=kv_valid,
        block_q=block_q, block_k=block_k, window=window, band=band,
    )
    if causal:
        grid = grid[:3] + (band.steps,)
        of_k = lambda bi, hi, qi, ki: (bi, _kv_head(hi, group), band.block(qi, ki), _I0)  # noqa: E731
    o_spec = pl.BlockSpec(
        (1, 1, block_q, dp), lambda bi, hi, qi, ki: (bi, hi, qi, _I0),
        memory_space=pltpu.VMEM,
    )
    if return_lse:
        out_specs = [
            o_spec,
            pl.BlockSpec(
                (1, 1, block_q, _LANES),
                lambda bi, hi, qi, ki: (bi, hi, qi, _I0),
                memory_space=pltpu.VMEM,
            ),
        ]
        out_shape = [
            _out_struct((b, h, t_q + pq, dp), q),
            _out_struct((b, h, t_q + pq, _LANES), q, dtype=jnp.float32),
        ]
        kfn = kernel
    else:
        # inference-only path: no lse buffer is declared or written — a
        # custom call's unused output would not be DCE'd and at bench shapes
        # the f32 lse would cost 2x the bytes of the bf16 output itself
        out_specs = o_spec
        out_shape = _out_struct((b, h, t_q + pq, dp), q)

        def kfn(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s):
            return kernel(q_ref, k_ref, v_ref, o_ref, None, m_s, l_s, acc_s)

    res = pl.pallas_call(
        kfn,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dp), lambda bi, hi, qi, ki: (bi, hi, qi, _I0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((1, 1, block_k, dp), of_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, dp), of_k, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, dp), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd" if window is None else "swa_fwd",
    )(q, k, v)
    if return_lse:
        out, lse = res
        # lse stays in padded lane-broadcast layout
        return out[:, :, :t_q, :d], lse
    return res[:, :, :t_q, :d]


def _rebuild_probs(
    q, k, lse, iq, ik, *, scale, causal, kv_valid, block_q, block_k, window=None, masked=True
):
    """Shared backward-pass probability reconstruction: the (bq, bk) score
    block, kv_valid + causal (+ window) masking, and ``p = exp(s − lse)`` — one
    definition so the dq and dk/dv kernels can never desynchronize. A block
    wholly inside the causal band (``masked`` false) takes no mask."""
    neg_inf = jnp.float32(NEG_INF)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.float32(scale)
    if not masked:
        return jnp.exp(s - lse)
    mask = _visible(
        iq, ik, causal=causal, kv_valid=kv_valid, block_q=block_q, block_k=block_k,
        window=window,
    )
    s = jnp.where(mask, s, neg_inf)
    p = jnp.where(mask, jnp.exp(s - lse), jnp.float32(0.0))
    return p


def _bwd_block_terms(
    refs, iq, ik, *, scale, causal, kv_valid, block_q, block_k, window=None, masked=True
):
    """Shared backward block math: unpack the (1, 1, blk, D) refs, rebuild
    p, compute ``dP = dO Vᵀ`` and ``dS = P ∘ (dP − D) · scale`` with the
    MXU-dtype casts — ONE definition so the dq, dk/dv, and fused kernels
    can never desynchronize. Returns (q, k, v, do, p_mx, ds_mx)."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref = refs
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, 0:1]  # (bq, 1)
    dd = dd_ref[0, 0][:, 0:1]

    p = _rebuild_probs(
        q, k, lse, iq, ik, scale=scale, causal=causal, kv_valid=kv_valid,
        block_q=block_q, block_k=block_k, window=window, masked=masked,
    )  # (bq, bk)
    p_mx = p if do.dtype == jnp.float32 else p.astype(do.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)
    ds = p * (dp - dd) * jnp.float32(scale)
    ds_mx = ds if q.dtype == jnp.float32 else ds.astype(q.dtype)
    return q, k, v, do, p_mx, ds_mx


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dq_acc,
    *, scale, causal, kv_valid, block_q, block_k, window=None, band=None,
):
    """dQ pass. Grid = (B, H, num_q_blocks, num_k_blocks), last sequential
    (with ``causal``: the steps of the key ``band``, as in the forward).

    p is rebuilt from the saved log-sum-exp (``p = exp(s − lse)``), then
    ``dS = P ∘ (dP − D)`` and ``dQ += scale · dS Kᵀ`` accumulate in VMEM
    scratch across the K axis — the standard flash backward, all four MXU
    dots in the input dtype with f32 accumulation.
    """
    iq = pl.program_id(2)
    ik = step = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        ik = band.lo(iq) + step
        live = ik <= band.hi(iq)
    else:
        live = ik >= 0

    def _accumulate(masked):
        _, k, _, _, _, ds_mx = _bwd_block_terms(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref), iq, ik,
            scale=scale, causal=causal, kv_valid=kv_valid,
            block_q=block_q, block_k=block_k, window=window, masked=masked,
        )
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds_mx, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(
        live, iq, ik, _accumulate, causal=causal, kv_valid=kv_valid, block_q=block_q,
        block_k=block_k, window=window,
    )

    @pl.when(step == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale, causal, kv_valid, block_q, block_k, q_blocks, group, window=None, band=None,
):
    """dK/dV pass. Grid = (B, H_kv, num_k_blocks, group * num_q_blocks), last
    sequential: the transposed-probability form — ``dV += Pᵀ dO`` and
    ``dK += scale · dSᵀ Q`` accumulate per K block across the Q axis, and
    across the ``group`` query heads that read this key-value head (their Q
    blocks follow one another on the sequential axis; ``q_blocks`` is how
    many one head has). With ``causal`` a head's steps are those of the
    query ``band`` of this key block (``q_blocks`` = ``band.steps``)."""
    ik = pl.program_id(2)
    step = pl.program_id(3)
    nq = pl.num_programs(3)
    # the q block inside its head; with one head a group the step itself
    iq = step if group == 1 else jax.lax.rem(step, np.int32(q_blocks))

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        iq = band.lo(ik) + iq
        live = iq <= band.hi(ik)
    else:
        live = iq >= 0

    def _accumulate(masked):
        # same (bq, bk) score orientation as the dq pass — the q-dim
        # contractions below transpose implicitly via dot_general dimension
        # numbers (no Mosaic-side transposes)
        q, _, _, do, p_mx, ds_mx = _bwd_block_terms(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref), iq, ik,
            scale=scale, causal=causal, kv_valid=kv_valid,
            block_q=block_q, block_k=block_k, window=window, masked=masked,
        )
        # dV += Pᵀ dO: contract the q dim of both operands
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p_mx, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dK += dSᵀ Q: contract the q dim
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds_mx, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(
        live, iq, ik, _accumulate, causal=causal, kv_valid=kv_valid, block_q=block_q,
        block_k=block_k, window=window,
    )

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_prologue(res, g, block_q, block_k):
    """Shared backward host-side prep: the D = rowsum(dO ∘ O) residual,
    block clamping/padding of every operand, and the lane-broadcast layout
    of dd and of the log-sum-exp column the forward rule kept. One definition
    for the two-pass and fused drivers."""
    q, k, v, out, lse = res
    b, h, t_q, d = q.shape
    t_k = k.shape[2]

    dd = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(axis=-1)
    qp, kp, vp, block_q, block_k, pq, pk, dp = _pad_blocks(
        q, k, v, t_q, t_k, d, block_q, block_k
    )
    pd_extra = dp - d
    if pq or pd_extra:
        do_p = jnp.pad(g, ((0, 0), (0, 0), (0, pq), (0, pd_extra)))
    else:
        do_p = g
    lanes = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pq)))[..., None] * jnp.ones((_LANES,), jnp.float32)  # noqa: E731
    dd_p = lanes(dd)
    with jax.named_scope("attn.lse"):  # the kept column back in the kernels' layout
        lse_p = lanes(lse)
    return qp, kp, vp, do_p, lse_p, dd_p, block_q, block_k, pq, pk, dp


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, dk_acc, dv_acc,
    *, scale, causal, kv_valid, block_q, block_k, window=None, band=None,
):
    """Single-pass backward. Grid = (B, H_kv, group, num_q_blocks,
    num_k_blocks), the last three sequential (with ``causal`` the last runs
    over the steps of the key ``band``, as in the forward and the dq pass).

    The two-pass backward rebuilds p and recomputes the dP dot once per
    pass — 7 MXU dots, two exp sweeps, and two full Q/K/V/dO streams per
    live block pair. Here each (qi, ki) pair is visited ONCE: p, dP, dS
    are shared, dQ accumulates in a per-qi scratch across the key axis (as
    in `_bwd_dq_kernel`) and dK, dV accumulate by a dynamic row-slice into
    float32 scratch that holds a whole key-value head, across the query
    blocks of the ``group`` query heads that read it, one head after the
    other: 5 dots, one exp sweep, one stream, and dk, dv leave the kernel
    summed over their group in the order `_bwd_dkv_kernel` sums them (query
    heads in turn, query blocks ascending). Costs VMEM: two (T_k, d) f32
    blocks stay resident beside the whole-head dk, dv output blocks they are
    cast into at a head's last step, which is what `_bwd_takes_fused`
    reckons."""
    g = pl.program_id(2)
    iq = pl.program_id(3)
    ik = step = pl.program_id(4)
    first_q = (g == 0) & (iq == 0)
    last_q = (g == pl.num_programs(2) - 1) & (iq == pl.num_programs(3) - 1)
    nk = pl.num_programs(4)

    @pl.when(first_q & (step == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(step == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        ik = band.lo(iq) + step
        live = ik <= band.hi(iq)
    else:
        live = ik >= 0

    def _accumulate(masked):
        q, k, _, do, p_mx, ds_mx = _bwd_block_terms(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref), iq, ik,
            scale=scale, causal=causal, kv_valid=kv_valid,
            block_q=block_q, block_k=block_k, window=window, masked=masked,
        )
        rows = pl.ds(pl.multiple_of(ik * block_k, block_k), block_k)
        dv_acc[rows, :] = dv_acc[rows, :] + jax.lax.dot_general(
            p_mx, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[rows, :] = dk_acc[rows, :] + jax.lax.dot_general(
            ds_mx, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds_mx, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(
        live, iq, ik, _accumulate, causal=causal, kv_valid=kv_valid, block_q=block_q,
        block_k=block_k, window=window,
    )

    @pl.when(step == nk - 1)
    def _finalize_q():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(last_q & (step == nk - 1))
    def _finalize_kv():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# A v5e TensorCore's VMEM is 128 MiB (Google Cloud's "TPU v5e" system
# architecture; Mosaic refuses a kernel that needs more in the described-v5e
# compile of tests/test_flash_bwd_fused.py), of which a kernel gets 16 MiB unasked.
# `nn/pallas_delta.py` asks for a fixed 64 MiB; the fused backward asks for what
# `_fused_bwd_vmem_bytes` reckons from its block shapes and runs where that is
# within this budget, which leaves the compiler a quarter of the chip's VMEM for
# what the reckoning does not see (its own temporaries, semaphores, spills).
_VMEM_BUDGET_BYTES = 96 * 2**20


def _fused_bwd_vmem_bytes(t_k_padded: int, dp: int, itemsize: int, block_q: int, block_k: int) -> int:
    """What `_bwd_fused_kernel` holds in VMEM: the two resident float32
    accumulators of a key-value head and the two whole-head output blocks they
    are cast into (an output's two buffers each); the streaming tiles (q, dO, dq
    by query block, k, v by key block, the two lane-broadcast float32 columns),
    two buffers each; dQ's scratch; and the four (block_q, block_k) float32
    intermediates of `_bwd_block_terms` (s, p, dP, dS)."""
    resident = 2 * t_k_padded * dp * (4 + 2 * itemsize)
    tiles = 2 * ((3 * block_q + 2 * block_k) * dp * itemsize + 2 * block_q * _LANES * 4)
    return resident + tiles + block_q * dp * 4 + 4 * block_q * block_k * 4


def _bwd_takes_fused(t_q: int, t_k: int, d: int, itemsize: int, block_q: int, block_k: int) -> bool:
    """The rule of `_flash_bwd_dispatch`'s ``"auto"``, from what the call can
    see (positions, head size, dtype, tiles; the group's sum is the kernel's
    own and a window keeps the resident blocks whole, so neither is a term):
    the fused kernel wherever its reckoned VMEM is within `_VMEM_BUDGET_BYTES`,
    the two passes past it (bfloat16 heads of 256 from 24,576 positions on)."""
    block_q, block_k, _, pk, pd = _block_geometry(t_q, t_k, d, block_q, block_k)
    return _fused_bwd_vmem_bytes(t_k + pk, d + pd, itemsize, block_q, block_k) <= _VMEM_BUDGET_BYTES


def _query_band(causal, window, t_q, t_k, block_q, block_k):
    """The band of query blocks a key block meets (None unless ``causal``)."""
    if not causal:
        return None
    return _Band(window or t_q + t_k, block_k, block_q, t_q // block_q, t_k // block_k, keys=False)


def _flash_bwd_fused(
    scale, causal, kv_valid, block_q, block_k, interpret, res, g, window=None
):
    """Fused-kernel backward; same contract as the two-pass `_flash_bwd`."""
    q, k, v = res[:3]
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    qp, kp, vp, do_p, lse_p, dd_p, block_q, block_k, pq, pk, dp = _bwd_prologue(
        res, g, block_q, block_k
    )

    tq_p, tk_p = t_q + pq, t_k + pk
    h_kv = k.shape[1]
    group = h // h_kv
    grid = (b, h_kv, group, tq_p // block_q, tk_p // block_k)
    keys = _key_band(causal, window, tq_p, tk_p, block_q, block_k)
    in_band = keys.block if causal else (lambda qi, ki: ki)
    if causal:
        grid = grid[:4] + (keys.steps,)
    spec = lambda block, index: pl.BlockSpec(block, index, memory_space=pltpu.VMEM)  # noqa: E731
    of_q = lambda bi, hi, gi, qi, ki: (bi, hi * np.int32(group) + gi, qi, _I0)  # noqa: E731
    of_k = lambda bi, hi, gi, qi, ki: (bi, hi, in_band(qi, ki), _I0)  # noqa: E731
    qo_spec, lm_spec = spec((1, 1, block_q, dp), of_q), spec((1, 1, block_q, _LANES), of_q)
    kv_spec = spec((1, 1, block_k, dp), of_k)
    # dk and dv: a key-value head's whole block, written when its last query head ends
    dkv_spec = spec((1, 1, tk_p, dp), lambda bi, hi, gi, qi, ki: (bi, hi, _I0, _I0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
            block_q=block_q, block_k=block_k, window=window, band=keys,
        ),
        grid=grid,
        in_specs=[qo_spec, kv_spec, kv_spec, qo_spec, lm_spec, lm_spec],
        out_specs=[qo_spec, dkv_spec, dkv_spec],
        out_shape=[
            _out_struct((b, h, tq_p, dp), q),
            _out_struct((b, h_kv, tk_p, dp), k),
            _out_struct((b, h_kv, tk_p, dp), v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.VMEM((tk_p, dp), jnp.float32),
            pltpu.VMEM((tk_p, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary", "arbitrary"),
            # the reckoning and a quarter more: past the 16 MiB a kernel gets unasked from 2,048 positions on
            vmem_limit_bytes=_fused_bwd_vmem_bytes(tk_p, dp, k.dtype.itemsize, block_q, block_k) * 5 // 4,
        ),
        interpret=interpret,
        name="flash_bwd_fused" if window is None else "swa_bwd_fused",
    )(qp, kp, vp, do_p, lse_p, dd_p)

    return (
        dq[:, :, :t_q, :d].astype(q.dtype),
        dk[:, :, :t_k, :d].astype(k.dtype),
        dv[:, :, :t_k, :d].astype(v.dtype),
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash(
    q, k, v, scale, causal, kv_valid, block_q, block_k, interpret, bwd_impl, window
):
    return _flash_forward(
        q, k, v, scale, causal, kv_valid, block_q, block_k, interpret, window=window
    )


def _flash_fwd(
    q, k, v, scale, causal, kv_valid, block_q, block_k, interpret, bwd_impl, window
):
    out, lse = _flash_forward(
        q, k, v, scale, causal, kv_valid, block_q, block_k, interpret,
        return_lse=True, window=window,
    )
    # the two residuals only this kernel can produce, named so that a
    # checkpoint round the caller can keep them (`KEPT_RESIDUALS`) and run the
    # kernel once; the log-sum-exp as one float a row, not the kernel's
    # lane-broadcast layout (128 times the bytes: twice the output's)
    out = checkpoint_name(out, KEPT_RESIDUALS[0])
    lse = checkpoint_name(lse[:, :, : q.shape[2], 0], KEPT_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_dispatch(
    scale, causal, kv_valid, block_q, block_k, interpret, bwd_impl, window, res, g
):
    """Pick the backward implementation. ``"auto"`` is `_bwd_takes_fused`: the
    fused single-pass kernel wherever the VMEM it reckons from the call's own
    shapes is within the chip's, else the two-pass kernels. The counters
    ``attn.bwd.fused`` and ``attn.bwd.two_pass`` say, once a traced backward
    call, which ran."""
    q, k = res[:2]
    if bwd_impl == "auto":
        fused = _bwd_takes_fused(q.shape[2], k.shape[2], q.shape[3], k.dtype.itemsize, block_q, block_k)
        bwd_impl = "fused" if fused else "two_pass"
    telemetry.get_registry().add(f"attn.bwd.{bwd_impl}")
    backward = _flash_bwd_fused if bwd_impl == "fused" else _flash_bwd
    return backward(scale, causal, kv_valid, block_q, block_k, interpret, res, g, window)


def _flash_bwd(scale, causal, kv_valid, block_q, block_k, interpret, res, g, window=None):
    """Flash backward as two Pallas kernels (dq; dk/dv) using the saved O
    and log-sum-exp — O(T) memory, every MXU dot in the input dtype (the
    r3 XLA-recompute backward ran true-f32 passes; this is the lm_step MFU
    lever)."""
    q, k, v = res[:3]
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    qp, kp, vp, do_p, lse_p, dd_p, block_q, block_k, pq, pk, dp = _bwd_prologue(
        res, g, block_q, block_k
    )

    h_kv = k.shape[1]
    group = h // h_kv
    grid_q = (b, h, (t_q + pq) // block_q, (t_k + pk) // block_k)
    of_k = lambda bi, hi, qi, ki: (bi, _kv_head(hi, group), ki, _I0)  # noqa: E731
    static = dict(scale=scale, causal=causal, kv_valid=kv_valid, block_q=block_q, block_k=block_k, window=window)
    keys = _key_band(causal, window, t_q + pq, t_k + pk, block_q, block_k)
    if causal:
        grid_q = grid_q[:3] + (keys.steps,)
        of_k = lambda bi, hi, qi, ki: (bi, _kv_head(hi, group), keys.block(qi, ki), _I0)  # noqa: E731
    qo_spec = pl.BlockSpec(
        (1, 1, block_q, dp), lambda bi, hi, qi, ki: (bi, hi, qi, _I0),
        memory_space=pltpu.VMEM,
    )
    kv_spec_q = pl.BlockSpec((1, 1, block_k, dp), of_k, memory_space=pltpu.VMEM)
    lm_spec_q = pl.BlockSpec(
        (1, 1, block_q, _LANES), lambda bi, hi, qi, ki: (bi, hi, qi, _I0),
        memory_space=pltpu.VMEM,
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static, band=keys),
        grid=grid_q,
        in_specs=[qo_spec, kv_spec_q, kv_spec_q, qo_spec, lm_spec_q, lm_spec_q],
        out_specs=qo_spec,
        out_shape=_out_struct((b, h, t_q + pq, dp), q),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq" if window is None else "swa_bwd_dq",
    )(qp, kp, vp, do_p, lse_p, dd_p)

    # dk/dv pass: K blocks on the parallel axis; the Q blocks of every query
    # head of the group sequential, so that dk and dv come out summed over them
    q_blocks = (t_q + pq) // block_q
    queries = _query_band(causal, window, t_q + pq, t_k + pk, block_q, block_k)
    if causal:
        q_blocks = queries.steps  # a head's steps: the band of this key block
    in_band = queries.block if causal else (lambda ki, qi: qi)
    grid_k = (b, h_kv, (t_k + pk) // block_k, group * q_blocks)
    if group == 1:
        of_q = lambda bi, hi, ki, qi: (bi, hi, in_band(ki, qi), _I0)  # noqa: E731
    else:
        def of_q(bi, hi, ki, step):
            nq = np.int32(q_blocks)
            return (bi, hi * np.int32(group) + jax.lax.div(step, nq), in_band(ki, jax.lax.rem(step, nq)), _I0)
    qo_spec_k = pl.BlockSpec((1, 1, block_q, dp), of_q, memory_space=pltpu.VMEM)
    kv_spec_k = pl.BlockSpec(
        (1, 1, block_k, dp), lambda bi, hi, ki, qi: (bi, hi, ki, _I0),
        memory_space=pltpu.VMEM,
    )
    lm_spec_k = pl.BlockSpec((1, 1, block_q, _LANES), of_q, memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static, q_blocks=q_blocks, group=group, band=queries),
        grid=grid_k,
        in_specs=[
            qo_spec_k, kv_spec_k, kv_spec_k, qo_spec_k, lm_spec_k, lm_spec_k,
        ],
        out_specs=[kv_spec_k, kv_spec_k],
        out_shape=[
            _out_struct((b, h_kv, t_k + pk, dp), k),
            _out_struct((b, h_kv, t_k + pk, dp), v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dp), jnp.float32),
            pltpu.VMEM((block_k, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv" if window is None else "swa_bwd_dkv",
    )(qp, kp, vp, do_p, lse_p, dd_p)

    return (
        dq[:, :, :t_q, :d].astype(q.dtype),
        dk[:, :, :t_k, :d].astype(k.dtype),
        dv[:, :, :t_k, :d].astype(v.dtype),
    )


_flash.defvjp(_flash_fwd, _flash_bwd_dispatch)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_valid: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    bwd_impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention as a hand-tiled Pallas TPU kernel.

    Same contract as :func:`heat_tpu.parallel.attention.local_attention`:
    ``(B, T, H, D)`` layout, f32 online softmax, K/V positions >= ``kv_valid``
    masked as padding. Blocks (``None``: the tuned tiles, 512 x 1024, with a
    window `_WINDOW_BLOCK_Q` x `_WINDOW_BLOCK_K`) are clamped for short
    sequences. ``k`` and ``v``
    may have fewer heads than ``q`` (grouped-query attention): query heads
    ``i * g .. i * g + g - 1`` read key-value head ``i``, by index and without
    a repeated copy, and ``dk``, ``dv`` come back summed over each group.
    ``interpret`` defaults to the Pallas interpreter when the default
    backend is not a TPU, so the same tests run on the CPU mesh;
    ``chip_smoke.py`` asserts the chip took the compiled side.

    ``bwd_impl`` selects the backward strategy: ``"auto"`` (the default) is
    the rule `_bwd_takes_fused`, read from the call's own shapes: the
    ``"fused"`` single-pass kernel (one probability rebuild a block pair,
    a key-value head's float32 dK and dV resident — see `_bwd_fused_kernel`)
    wherever the VMEM it reckons is within the chip's, the ``"two_pass"``
    dq + dk/dv kernels past it (bfloat16 heads of 256 from 24,576 positions
    on). On a TPU v5e the fused kernel takes 0.70–0.74 of the two passes' time
    at every training cell's shape (heads of 64 to 256, groups of 1 to 8,
    4,096 to 16,384 positions, with and without a window; PERF.md section 6,
    PR 42); ``"two_pass"`` and ``"fused"`` force a path, for the tests.

    ``causal``: the grid's key axis (for the dk/dv pass the query axis) covers the
    blocks up to the diagonal alone, its block index computed from the other
    axis's (a row's steps past its diagonal name the diagonal's block again and
    copy nothing), and blocks wholly under the diagonal take no mask. ``window``
    (with ``causal``): position ``t`` sees the keys ``t - window < j <= t``,
    itself and the ``window - 1`` before it: the same grid over the band that a
    lower edge leaves, the kernels named ``swa_fwd``,
    ``swa_bwd_fused`` (``swa_bwd_dq``, ``swa_bwd_dkv``) where ``window=None``
    keeps ``flash_fwd``, ``flash_bwd_fused`` (``flash_bwd_dq``, ``flash_bwd_dkv``).
    Without ``causal`` every block is visited.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (B, T, H, D) inputs, got {q.shape}")
    # kernel works in (B, H, T, D); public layout is (B, T, H, D)
    out = flash_attention_head_major(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, scale=scale, kv_valid=kv_valid, block_q=block_q, block_k=block_k, interpret=interpret,
        bwd_impl=bwd_impl, window=window,
    )
    return out.transpose(0, 2, 1, 3)


def flash_attention_head_major(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_valid: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    bwd_impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """:func:`flash_attention` in the kernels' own layout, ``(B, H, T, D)`` in
    and out, for a caller that holds its operands so already
    (:mod:`heat_tpu.nn.pallas_qk_prep` writes queries and keys head-major)."""
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads do not divide over {k.shape[1]} key and {v.shape[1]} value heads"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = q.shape[-1]
    t_k = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_valid = t_k if kv_valid is None else int(kv_valid)
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError(f"a window (got {window!r}) is a whole number of positions >= 1 and needs causal=True")
        window = int(window)
    block_q, block_k = _tiles(window, block_q, block_k)
    if bwd_impl not in ("two_pass", "fused", "auto"):
        raise ValueError(
            f"bwd_impl must be 'two_pass', 'fused' or 'auto', got {bwd_impl!r}"
        )
    return _flash(q, k, v, scale, causal, kv_valid, block_q, block_k, interpret, bwd_impl, window)


def causal_grid(t_q: int, t_k: int, block_q: Optional[int] = None, block_k: Optional[int] = None, window: Optional[int] = None):
    """``(visited, streamed, live)`` of the causal forward kernel's grid for
    one head of ``t_q`` queries on ``t_k`` keys: its steps, those of them that
    name another key block than the step before (what is copied) and the key
    blocks that hold a pair some query sees (a grid that copies the band and
    no more: the last two equal)."""
    block_q, block_k, pq, pk, _ = _block_geometry(t_q, t_k, _LANES, *_tiles(window, block_q, block_k))
    band = _key_band(True, window, t_q + pq, t_k + pk, block_q, block_k)
    return band.visited, band.streamed, band.live


def window_grid(t_q: int, t_k: int, window: int, block_q: Optional[int] = None, block_k: Optional[int] = None):
    """``(visited, live)`` of :func:`causal_grid` for the windowed form."""
    visited, _, live = causal_grid(t_q, t_k, block_q, block_k, window)
    return visited, live
