"""Long-context attention over a sequence-sharded mesh.

The reference framework has no transformer code; its parity mechanisms are
the ring block schedule (reference heat/spatial/distance.py:280-326) and the
axis-aware Alltoall (reference heat/core/communication.py:1180-1322). This
module is the capability those mechanisms exist for, built TPU-first:

* :func:`ring_attention` — blockwise softmax(QKᵀ)V with K/V blocks circulated
  around the ICI ring (`ppermute`) and flash-style online renormalization, so
  a sequence of length T sharded p ways never materializes a (T, T) matrix
  and each chip holds O(T/p) activations.
* :func:`ulysses_attention` — `all_to_all` swaps the sharded axis from
  sequence to heads, runs dense local attention per head group, and swaps
  back. Cheaper per step than the ring when heads ≥ p, at the cost of two
  all_to_alls.
* :func:`local_attention` — the single-device blockwise kernel both build on.

Shapes follow jax convention ``(batch, seq, heads, head_dim)``; the sharded
axis is ``seq`` (axis 1) on input and output for both distributed variants.
All kernels are jit-pure and differentiable (the backward pass re-runs the
ring under autodiff; `jax.checkpoint` the caller for O(T/p) memory).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, q_start, k_start, scale, causal, kv_len_valid, window=None):
    """One flash-attention accumulation step on local blocks.

    q: (B, Tq, H, D); k, v: (B, Tk, H, D); m, l: (B, H, Tq); o like q.
    ``q_start``/``k_start`` are the blocks' global sequence offsets (traced
    scalars) used for causal masking; ``kv_len_valid`` masks K tail padding;
    with a ``window`` a query sees the ``window`` keys that end at itself.
    """
    # MXU dots run in the INPUT dtype with f32 accumulation — an up-front
    # astype(f32) would force true-f32 MXU passes at ~1/4 throughput (the
    # r3 lm_step/backward bottleneck); softmax stays f32 throughout
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    tk = k.shape[1]
    k_pos = k_start + jnp.arange(tk)
    mask = k_pos[None, :] < kv_len_valid  # (1, Tk) — valid K positions
    if causal:
        q_pos = q_start + jnp.arange(q.shape[1])
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, None, :, :], s, NEG_INF)

    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: keep m finite so exp() stays well-defined
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask[None, None, :, :], p, 0.0)
    alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - m_safe)
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
    l_new = alpha * l + p.sum(axis=-1)
    # PV in v's dtype (standard flash practice): f32 probabilities round to
    # bf16 on the way into the MXU for bf16 v, accumulating in f32
    p_mx = p if v.dtype == jnp.float32 else p.astype(v.dtype)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p_mx, v, preferred_element_type=jnp.float32
    )
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _finalize(m, l, o):
    denom = jnp.where(l == 0.0, 1.0, l)
    return o / denom.transpose(0, 2, 1)[..., None]


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
    kv_valid: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise (flash) attention on one device. ``(B, T, H, D)`` layout.

    K/V are processed in ``block_size`` chunks with online softmax — the same
    accumulator the distributed variants carry around the ring, so numerics
    are identical across all three entry points. K/V positions ``>= kv_valid``
    are treated as padding and masked out. ``window`` (with ``causal``):
    position ``t`` sees the keys ``t - window < j <= t`` (a mask here: every
    key block is visited; the Pallas kernels skip the blocks outside the band).
    """
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"a window (got {window!r}) is a whole number of positions >= 1 and needs causal=True")
    b, tq, h, d = q.shape
    tk = k.shape[1]
    kv_valid = tk if kv_valid is None else kv_valid
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    nblk = max(1, -(-tk // block_size))
    pad = nblk * block_size - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # derive the accumulators from q (zeros_like-style) so that when this
    # kernel runs inside a shard_map the carry inherits q's device-varying
    # type — a literal jnp.zeros would be replicated and break the fori_loop
    # carry typing
    zero_q = jnp.zeros_like(q, dtype=jnp.float32)
    m = zero_q.sum(axis=-1).transpose(0, 2, 1) + NEG_INF  # (B, H, Tq)
    l = zero_q.sum(axis=-1).transpose(0, 2, 1)
    o = zero_q

    def body(i, carry):
        m, l, o = carry
        k_start = i * block_size
        kb = jax.lax.dynamic_slice_in_dim(k, k_start, block_size, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, k_start, block_size, axis=1)
        # inputs keep their dtype: the MXU dots inside _block_attn accumulate
        # in f32 via preferred_element_type (bf16 inputs run full-rate)
        return _block_attn(
            q, kb, vb, m, l, o, 0, k_start, scale, causal, kv_valid, window,
        )

    m, l, o = jax.lax.fori_loop(0, nblk, body, (m, l, o))
    return _finalize(m, l, o).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    comm,
    causal: bool = False,
    scale: Optional[float] = None,
    seq_len: Optional[int] = None,
) -> jax.Array:
    """Ring attention over a sequence-sharded mesh (Liu et al. 2023).

    ``q``, ``k``, ``v``: ``(B, T_pad, H, D)`` sharded along axis 1 over
    ``comm``'s mesh (``T_pad`` divisible by ``comm.size``; positions
    ``>= seq_len`` are padding and are masked out of the softmax). Each mesh
    position keeps its Q block stationary and circulates its K/V block one
    hop per step; the flash accumulator makes the p partial softmaxes exact.
    Communication rides ICI and overlaps with the per-step MXU work.
    """
    p = comm.size
    axis = comm.axis_name
    b, t_pad, h, d = q.shape
    seq_len = t_pad if seq_len is None else seq_len
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    tc = t_pad // p
    perm = [(i, (i + 1) % p) for i in range(p)]

    def kernel(qb, kb, vb):
        rank = jax.lax.axis_index(axis)
        m = jnp.full((b, h, tc), NEG_INF, dtype=jnp.float32)
        l = jnp.zeros((b, h, tc), dtype=jnp.float32)
        o = jnp.zeros((b, tc, h, d), dtype=jnp.float32)
        # freshly-built accumulators are replicated; the scan carry must be
        # device-varying because it mixes with the sharded q/k/v blocks
        m, l, o = (jax.lax.pcast(a, (axis,), to="varying") for a in (m, l, o))

        def body(t, carry):
            kc, vc, m, l, o = carry
            origin = (rank - t) % p
            m, l, o = _block_attn(
                qb, kc, vc,
                m, l, o, rank * tc, origin * tc, scale, causal, seq_len,
            )
            # the K/V hops ride the wrapper chokepoint (ISSUE 15: the
            # cost model prices them — ring_attention_cost — and the
            # HLO auditor sees them); exact pinned: a compressed block
            # would re-quantize p times around the ring and drift the
            # softmax renormalization
            kc = comm.ppermute(kc, perm, precision="off")
            vc = comm.ppermute(vc, perm, precision="off")
            return (kc, vc, m, l, o)

        kc, vc, m, l, o = jax.lax.fori_loop(0, p, body, (kb, vb, m, l, o))
        return _finalize(m, l, o).astype(qb.dtype)

    spec = comm.spec(1, 4)
    return jax.shard_map(
        kernel, mesh=comm.mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    comm,
    causal: bool = False,
    scale: Optional[float] = None,
    seq_len: Optional[int] = None,
    block_size: int = 512,
    use_pallas: bool = False,
) -> jax.Array:
    """Ulysses sequence parallelism (Jacobs et al. 2023).

    ``all_to_all`` swaps sharding sequence→heads (each position then holds
    the full sequence for H/p heads), runs the dense blockwise kernel, and
    swaps back. This is the TPU-native form of the reference's axis-aware
    Alltoall reshard (reference heat/core/communication.py:1180-1322).
    Requires ``H`` divisible by ``comm.size``. ``use_pallas=True`` runs the
    local step through the hand-tiled Pallas kernel
    (:func:`heat_tpu.parallel.flash_attention`, ~2.7× the XLA path on v5e)
    at its tuned tile sizes — ``block_size`` applies to the XLA path only.
    """
    p = comm.size
    b, t_pad, h, d = q.shape
    if h % p != 0:
        raise ValueError(f"heads ({h}) must divide over mesh size ({p})")
    seq_len = t_pad if seq_len is None else seq_len
    def kernel(qb, kb, vb):
        # (B, T/p, H, D) -> (B, T, H/p, D): gather seq, scatter heads.
        # Wrapper-routed (ISSUE 15): the exchanges are priced by
        # ulysses_attention_cost and lower tiered under
        # HEAT_TPU_HIERARCHICAL; exact pinned — Q/K/V bits feed the
        # softmax, compression belongs to the collective, not here.
        a2a = functools.partial(
            comm.all_to_all, split_axis=2, concat_axis=1, precision="off",
        )
        qh, kh, vh = a2a(qb), a2a(kb), a2a(vb)
        if use_pallas:
            from .pallas_attention import flash_attention

            oh = flash_attention(
                qh, kh, vh, causal=causal, scale=scale, kv_valid=seq_len,
            )
        else:
            oh = local_attention(
                qh, kh, vh, causal=causal, scale=scale, block_size=block_size,
                kv_valid=seq_len,
            )
        back = functools.partial(
            comm.all_to_all, split_axis=1, concat_axis=2, precision="off",
        )
        return back(oh)

    spec = comm.spec(1, 4)
    out = jax.shard_map(
        kernel, mesh=comm.mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
    return out
