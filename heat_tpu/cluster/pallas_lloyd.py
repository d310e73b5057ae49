"""Fused Pallas TPU kernel for the Lloyd (K-Means) iteration.

The XLA Lloyd step (:func:`heat_tpu.cluster.kmeans._lloyd_step`) is already
one compiled program, but it materializes two (n, k) f32 intermediates per
iteration (the distance matrix and the one-hot matrix) — at bench shapes
(n=2M, d=k=64) that is ~5 HBM round trips over X's own footprint, and the
r4 bench measured 4.5 TF/s counted against a ~50 TF/s bandwidth roofline.

This kernel runs the whole accumulation in one pass over X: for each row
block the assignment scores, argmin, and the (k, d)/(k,) sums+counts
updates all happen on the tile while it is in VMEM — X is read exactly
ONCE per Lloyd iteration and nothing (n, k)-sized ever touches HBM.

MXU dots per block (scores: (bm,d)x(d,k); update: (k,bm)x(bm,d)), both
with f32 accumulation. The argmin drops the ||x||^2 term (constant per
row — it cannot change the winner), so scores are just c2 - 2 x.c with
the manual ``"bf16x3"`` split product by default (HIGH-class accuracy —
the guard from ``_kcluster._d2`` — via MXU-guaranteed DEFAULT-tier dots,
see pallas_util.dot_f32).

Scope: TPU f32 fits — single-device directly, multi-device via
`lloyd_fit_pallas_sharded` (shard_map over row shards + one psum of the
sums/counts per iteration, the same single-collective shape as the XLA
fit). The final labels/inertia pass stays on the XLA `_d2` form — one
extra pass at the end of the fit is noise across max_iter iterations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.pallas_util import DotPrecision, dot_f32

__all__ = [
    "lloyd_fit_pallas",
    "lloyd_fit_pallas_sharded",
    "pallas_lloyd_applicable",
]

_I0 = np.int32(0)  # i32 index-map literal (jax_enable_x64 guard)
_MAX_D = 512
_MAX_K = 1024


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _lloyd_kernel(
    lim_ref, x_ref, c_ref, c2_ref, sums_ref, counts_ref, sums_s, counts_s,
    *, bm, k, precision,
):
    """Grid = (num_row_blocks,), sequential. Scratch (sums, counts)
    accumulates across blocks; written out at the last block. ``lim_ref``
    holds this buffer's LOCAL valid-row count — rows at or past it (the
    global tail pad on the last shards, plus any local block-size
    round-up pad) drop out of sums and counts."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        sums_s[:] = jnp.zeros_like(sums_s)
        counts_s[:] = jnp.zeros_like(counts_s)

    xb = x_ref[:]  # (bm, dp) f32
    c = c_ref[:]  # (kp, dp) f32
    # ``precision`` (a tier or "bf16x3") for the scores dot is swept
    # on-chip by scripts/tpu_tune.py (Mosaic lowering cost per strategy
    # is not uniform; see pallas_util.dot_f32)
    dot = dot_f32(xb, c, (((1,), (1,)), ((), ())), precision)  # (bm, kp)
    # ||c||^2 arrives as a lane-major (8, kp) input: reducing c*c over
    # lanes in here leaves a sublane vector, and Mosaic's relayout of it
    # to the (1, kp) row the broadcast needs costs ~64 KB of scoped VMEM
    # per block row (32 MB at bm=512, over the 16 MiB limit)
    score = c2_ref[0:1, :] - jnp.float32(2.0) * dot  # argmin-equiv. to d2
    jidx = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    score = jnp.where(jidx < k, score, jnp.float32(3.4e38))  # mask center pads
    # first-minimum index as two lane reductions: jnp.argmin yields i64
    # under jax_enable_x64, which Mosaic refuses
    smin = jnp.min(score, axis=1, keepdims=True)
    labels = jnp.min(
        jnp.where(score == smin, jidx, jnp.int32(score.shape[1])),
        axis=1, keepdims=True,
    )  # (bm, 1)
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    valid = row < lim_ref[0]
    onehot = jnp.where(
        (labels == jidx) & valid, jnp.float32(1.0), jnp.float32(0.0)
    )  # (bm, kp)
    # the update dot carries the same guard: onehot is exact in bf16, so
    # the split product recovers f32-class center sums — a bare DEFAULT
    # dot would bake ~2^-9 operand rounding into every center coordinate
    sums_s[:] += dot_f32(
        onehot, xb, (((0,), (0,)), ((), ())), precision
    )  # (kp, dp)
    counts_s[:] += jnp.broadcast_to(
        jnp.sum(onehot, axis=0, keepdims=True), counts_s.shape
    )

    @pl.when(i == nb - 1)
    def _flush():
        sums_ref[:] = sums_s[:]
        counts_ref[:] = counts_s[:]


def _lloyd_update(x, centers_pad, n, k, bm, interpret, lim=None,
                  precision: DotPrecision = "bf16x3"):
    """One fused accumulation pass: (sums (kp, dp), counts (8, kp)).
    ``x`` must already be padded to (mp, dp) with mp % bm == 0;
    ``centers_pad`` to (kp, dp); ``lim`` is the LOCAL valid-row count
    (defaults to the global n — correct outside shard_map)."""
    mp, dp = x.shape
    kp = centers_pad.shape[0]
    if lim is None:
        lim = jnp.full((1,), n, jnp.int32)
    c2 = jnp.broadcast_to(
        jnp.sum(centers_pad * centers_pad, axis=1)[None, :], (8, kp)
    )
    return pl.pallas_call(
        functools.partial(_lloyd_kernel, bm=bm, k=k, precision=precision),
        grid=(mp // bm,),
        in_specs=[
            # explicit i32 index map: a bare SMEM BlockSpec synthesizes a
            # default map whose literals trace as i64 under jax_enable_x64,
            # which Mosaic cannot legalize ("func.return(i64)")
            pl.BlockSpec((1,), lambda i: (_I0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, dp), lambda i: (i, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((kp, dp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, kp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((kp, dp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, kp), lambda i: (_I0, _I0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((8, kp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kp, dp), jnp.float32),
            pltpu.VMEM((8, kp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="lloyd_update",
    )(lim.astype(jnp.int32), x, centers_pad, c2)


@functools.partial(
    jax.jit,
    static_argnames=("n", "max_iter", "block_m", "interpret", "precision"),
)
def lloyd_fit_pallas(
    xb: jax.Array,
    centers0: jax.Array,
    n: int,
    max_iter: int,
    tol,
    block_m: int = 512,
    interpret: bool = False,
    precision: DotPrecision = "bf16x3",
):
    """The whole K-Means fit with the fused update kernel inside a
    `lax.while_loop`; returns (centers (k, d), labels (m,), inertia,
    n_iter) with the same semantics as `kmeans._lloyd_fit` (labels/inertia
    from one final XLA `_d2` pass over the converged centers)."""
    from ._kcluster import _d2

    m, d = xb.shape
    k = centers0.shape[0]
    # feature lanes pad at 64-granularity (like 64-wide attention
    # heads): d=64 stays unpadded — a 128 pad would double X's HBM
    # footprint and read traffic at the bench shapes
    dp, kp = _round_up(d, 64), _round_up(k, 128)
    bm = min(block_m, _round_up(m, 8))
    mp = _round_up(m, bm)
    xp = jnp.pad(xb.astype(jnp.float32), ((0, mp - m), (0, dp - d)))
    c0 = jnp.pad(centers0.astype(jnp.float32), ((0, kp - k), (0, dp - d)))

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(carry):
        c, it, _ = carry
        sums, counts = _lloyd_update(xp, c, n, k, bm, interpret,
                                     precision=precision)
        cnt = counts[0:1, :].T  # (kp, 1); center pads stay 0
        new_c = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c)
        shift = jnp.sum((new_c - c) ** 2)
        return new_c, it + 1, shift

    cpad, n_iter, _ = jax.lax.while_loop(
        cond, body, (c0, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
    )
    centers = cpad[:k, :d].astype(xb.dtype)
    # final assignment on the XLA form (one pass; exact d2 for inertia)
    w = (jnp.arange(m) < n).astype(xb.dtype)
    d2 = _d2(xb, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    return centers, labels, inertia, n_iter


@functools.partial(
    jax.jit,
    static_argnames=(
        "comm", "n", "max_iter", "block_m", "interpret", "precision"
    ),
)
def lloyd_fit_pallas_sharded(
    comm,
    xb: jax.Array,
    centers0: jax.Array,
    n: int,
    max_iter: int,
    tol,
    block_m: int = 512,
    interpret: bool = False,
    precision: DotPrecision = "bf16x3",
):
    """Multi-device variant: the fused update runs per row-shard inside
    `shard_map` and one psum per iteration merges the (k, d)+(k,)
    sums/counts — the same single-collective-per-Lloyd-iteration shape as
    the XLA fit (and the reference's Allreduce, kmeans.py:73). Centers
    carry replicated through the while_loop; labels/inertia come from one
    final XLA `_d2` pass on the sharded buffer outside the shard_map."""
    from ._kcluster import _d2

    p = comm.size
    m, d = xb.shape
    k = centers0.shape[0]
    # feature lanes pad at 64-granularity (like 64-wide attention
    # heads): d=64 stays unpadded — a 128 pad would double X's HBM
    # footprint and read traffic at the bench shapes
    dp, kp = _round_up(d, 64), _round_up(k, 128)
    c_rows = m // p  # physical buffer rows divide the mesh by invariant
    bm = min(block_m, _round_up(c_rows, 8))
    c0 = jnp.pad(centers0.astype(jnp.float32), ((0, kp - k), (0, dp - d)))

    def shard_fn(xs, c0_):
        rank = comm.axis_index()
        # local valid rows: global logical rows falling inside this shard
        lim = jnp.clip(n - rank * c_rows, 0, c_rows).astype(jnp.int32).reshape((1,))
        mp_l = _round_up(c_rows, bm)
        xp = jnp.pad(xs.astype(jnp.float32), ((0, mp_l - c_rows), (0, dp - d)))

        def cond(carry):
            _, it, shift = carry
            return jnp.logical_and(it < max_iter, shift > tol)

        def body(carry):
            c, it, _ = carry
            sums, counts = _lloyd_update(xp, c, n, k, bm, interpret, lim,
                                         precision=precision)
            # comm wrapper (not raw lax.psum) so the hop is visible to
            # the HLO auditor/cost model; pinned exact — centroid
            # accumulation predates the collective-precision knob and a
            # compressed wire would move the fixed point (heatlint HL002)
            sums = comm.psum(sums, precision="off")
            counts = comm.psum(counts, precision="off")
            cnt = counts[0:1, :].T
            new_c = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c)
            shift = jnp.sum((new_c - c) ** 2)
            return new_c, it + 1, shift

        cpad, n_iter, _ = jax.lax.while_loop(
            cond, body, (c0_, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
        )
        return cpad, n_iter

    cpad, n_iter = jax.shard_map(
        shard_fn,
        mesh=comm.mesh,
        in_specs=(comm.spec(0, 2), comm.spec(None, 2)),
        out_specs=(comm.spec(None, 2), comm.spec(None, 0)),
        check_vma=False,
    )(xb, c0)
    centers = cpad[:k, :d].astype(xb.dtype)
    w = (jnp.arange(m) < n).astype(xb.dtype)
    d2 = _d2(xb, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    return centers, labels, inertia, n_iter


def pallas_lloyd_applicable(comm_size: int, split, d: int, k: int, jnp_dtype) -> bool:
    """TPU f32 fits with blocks that fit VMEM; multi-device needs the
    sample buffer row-sharded (split=0)."""
    return (
        jax.default_backend() == "tpu"
        and (comm_size == 1 or split == 0)
        and d <= _MAX_D
        and k <= _MAX_K
        and jnp_dtype == jnp.float32
    )
