"""Pallas TPU kernel: single-HBM-read column moments (mean + M2).

``ht.var`` is the numerically-safe two-pass form (mean, then centered
square sum) — under one jit that is two full HBM reads of X, capping the
statistical-moments benchmark at ~50% of the bandwidth roofline. This
kernel computes both moments in ONE pass using the chunk-parallel Welford
combine (the same merge rule the reference applies across MPI ranks,
statistics.py:803-828, applied here across row blocks): each block's
(count, mean, M2) is computed stably in VMEM and merged into running
accumulators — X is read exactly once and the result matches the two-pass
form to f32 accuracy (no E[x^2]-E[x]^2 cancellation).

Wired into :func:`heat_tpu.core.statistics.var` (and through it ``std``)
for the single-device TPU f32 axis-0 reduction on 2-D arrays — the
benchmark shape and the common "feature statistics" case. Everything else
keeps the two-pass form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "chan_merge",
    "column_moments",
    "sharded_column_moments",
    "pallas_moments_applicable",
]

_I0 = np.int32(0)
_MAX_D = 4096
# f32 bytes of one (bm, dp) row block. The kernel holds the block double-
# buffered plus ~4 block-sized temporaries (masked copy, centered copy,
# squares), all inside v5e's 16 MiB scoped-VMEM limit: 1024 x 4096 blocks
# asked Mosaic for 32 MiB.
_BLOCK_BYTES = 1 << 20


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def chan_merge(na, mean_a, m2_a, nb, mean_b, m2_b):
    """Chan/Welford pairwise combine of two (count, mean, M2) moment
    carries — the SAME merge rule the kernel applies across row blocks
    (``_moments_kernel``) and :func:`sharded_column_moments` applies
    across shards, exposed as the mergeable-carry algebra of
    :class:`heat_tpu.streaming.StreamingMoments`: ``partial_fit`` chunks
    combine associatively through this exact formula, so a resumed
    stream reproduces the uninterrupted carry bit-for-bit. Host-side
    arithmetic (python/numpy operands — the streaming carry is kept in
    float64 on the host); an empty pair (``tot == 0``) passes the left
    side through unchanged."""
    tot = na + nb
    if float(tot) == 0.0:
        return tot, mean_a, m2_a
    delta = mean_b - mean_a
    mean = mean_a + delta * (nb / tot)
    m2 = m2_a + m2_b + delta * delta * (na * nb / tot)
    return tot, mean, m2


def _moments_kernel(lim_ref, x_ref, mean_ref, m2_ref, mean_s, m2_s, cnt_s, *, bm):
    """Grid = (num_row_blocks,), sequential; Welford-combine across blocks."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        mean_s[:] = jnp.zeros_like(mean_s)
        m2_s[:] = jnp.zeros_like(m2_s)
        cnt_s[0] = jnp.float32(0.0)

    xb = x_ref[:]  # (bm, dp) f32
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    # LOCAL valid-row count (inside shard_map each shard passes its own
    # limit; block round-up pads past it drop out)
    valid = (row < lim_ref[0]).astype(jnp.float32)  # (bm, 1)
    # this block's valid rows, in scalar arithmetic: the predicate and the
    # SMEM count below must not come out of a vector reduction
    nv_i = jnp.minimum(jnp.maximum(lim_ref[0] - i * bm, 0), bm)
    nv = nv_i.astype(jnp.float32)

    @pl.when(nv_i > 0)
    def _combine():
        xv = xb * valid
        bsum = jnp.sum(xv, axis=0, keepdims=True)  # (1, dp)
        bmean = bsum / nv
        d = (xb - bmean) * valid
        bm2 = jnp.sum(d * d, axis=0, keepdims=True)  # (1, dp)
        cnt = cnt_s[0]
        tot = cnt + nv
        delta = bmean - mean_s[0:1, :]
        mean_new = mean_s[0:1, :] + delta * (nv / tot)
        m2_new = m2_s[0:1, :] + bm2 + delta * delta * (cnt * nv / tot)
        mean_s[:] = jnp.broadcast_to(mean_new, mean_s.shape)
        m2_s[:] = jnp.broadcast_to(m2_new, m2_s.shape)
        cnt_s[0] = tot

    @pl.when(i == nb - 1)
    def _flush():
        mean_ref[:] = mean_s[:]
        m2_ref[:] = m2_s[:]


@functools.partial(
    jax.jit, static_argnames=("n", "block_m", "interpret", "pre_map")
)
def column_moments(
    x: jax.Array, n: int, block_m: int = 1024, interpret: bool = False,
    lim=None, pre_map=None,
):
    """(mean (d,), M2 (d,)) over the first axis of an (m, d) f32 array,
    counting only the first ``n`` rows (tail-pad aware). One HBM read.

    ``pre_map`` (static) grafts a single-array elementwise prologue into
    the same program — the moments of ``pre_map(x)`` from one read of
    ``x``. This is the DIRECT-caller graft slot; the statistics layer's
    chain grafting (``statistics._pallas_moments_fused``) instead
    composes the pending chain around this kernel at the program level
    (site ``fusion_moments``): chain scalars are *runtime* arguments
    there (programs shared across scalar values — baking them into a
    static ``pre_map`` closure would fork one executable per value), and
    the pad mask must apply to GLOBAL row indices, which a per-shard
    ``pre_map`` inside ``shard_map`` cannot express. ``pre_map`` output
    must be finite on rows past ``n`` (the validity multiply would turn
    ``0·inf`` into NaN)."""
    if pre_map is not None:
        x = pre_map(x)
    m, d = x.shape
    dp = _round_up(d, 64)  # 64-lane granularity: d=64 stays unpadded
    bm = min(
        block_m, _round_up(m, 8), max(8, _BLOCK_BYTES // (dp * 4) // 8 * 8)
    )
    mp = _round_up(m, bm)
    if (mp, dp) != (m, d):
        x = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, dp - d)))
    else:
        x = x.astype(jnp.float32)
    if lim is None:
        lim = jnp.full((1,), n, jnp.int32)
    mean_o, m2_o = pl.pallas_call(
        functools.partial(_moments_kernel, bm=bm),
        grid=(mp // bm,),
        in_specs=[
            # explicit i32 index map: a bare SMEM BlockSpec synthesizes a
            # default map whose literals trace as i64 under jax_enable_x64,
            # which Mosaic cannot legalize ("func.return(i64)")
            pl.BlockSpec((1,), lambda i: (_I0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, dp), lambda i: (i, _I0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((8, dp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, dp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, dp), jnp.float32),
            jax.ShapeDtypeStruct((8, dp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, dp), jnp.float32),
            pltpu.VMEM((8, dp), jnp.float32),
            pltpu.SMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(lim.astype(jnp.int32), x)
    return mean_o[0, :d], m2_o[0, :d]


@functools.partial(
    jax.jit, static_argnames=("comm", "n", "block_m", "interpret", "pre_map")
)
def sharded_column_moments(
    comm, x: jax.Array, n: int, block_m: int = 1024, interpret: bool = False,
    pre_map=None,
):
    """Multi-device variant: per-shard (count, mean, M2) from the fused
    kernel, then the closed-form Welford merge across shards with two
    psums — mean_g = psum(n_s mean_s)/n; M2_g = psum(M2_s) +
    psum(n_s (mean_s - mean_g)^2). X is still read exactly once.
    ``pre_map`` applies per shard before the kernel (elementwise, so
    shard-local) — see :func:`column_moments`."""
    p = comm.size
    m, _d = x.shape
    c_rows = m // p

    def shard_fn(xs):
        rank = comm.axis_index()
        lim = jnp.clip(n - rank * c_rows, 0, c_rows).astype(jnp.int32)
        mean_s, m2_s = column_moments(
            xs, n, block_m=block_m, interpret=interpret,
            lim=lim.reshape((1,)), pre_map=pre_map,
        )
        ns = lim.astype(jnp.float32)
        # comm wrapper (not raw lax.psum) so the hops are visible to the
        # HLO auditor/cost model; pinned exact — the Chan/Welford merge is
        # bit-pinned by tests and predates the collective-precision knob
        # (heatlint HL002)
        mean_g = comm.psum(ns * mean_s, precision="off") / jnp.float32(n)
        dlt = mean_s - mean_g
        m2_g = comm.psum(m2_s + ns * dlt * dlt, precision="off")
        return mean_g, m2_g

    return jax.shard_map(
        shard_fn,
        mesh=comm.mesh,
        in_specs=(comm.spec(0, 2),),
        out_specs=(comm.spec(None, 1), comm.spec(None, 1)),
        check_vma=False,
    )(x)


def pallas_moments_applicable(comm_size: int, split, ndim: int, axis, d: int, jnp_dtype) -> bool:
    """TPU f32 axis-0 reductions on 2-D arrays; multi-device needs the
    rows sharded (split=0)."""
    return (
        jax.default_backend() == "tpu"
        and (comm_size == 1 or split == 0)
        and ndim == 2
        and axis == 0
        and d <= _MAX_D
        and jnp_dtype == jnp.float32
    )
