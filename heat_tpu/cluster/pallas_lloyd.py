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

One algorithm, two orientations of the same block walk, chosen from the
feature width ``d`` alone (:func:`lloyd_form`), because that is what
decides how X lies on the chip:

* ``d % 128 != 0`` — **feature-major**. The TPU's default layout of an
  ``(n, d)`` f32 array is then ``{0,1:T(8,128)}``: features on sublanes,
  rows on lanes, compact. ``xb.T`` inside the jitted fit is a bitcast of
  it, and the kernel takes ``(d, bn)`` blocks
  of ``X.T`` (the first dimension is the whole of ``d``, so no feature is
  padded for any ``d``). Clusters sit on sublanes, ``k`` rounded up to 8:
  scores are ``(kp8, bn)``, the argmin is a sublane reduction, and the
  update ``onehot (kp8, bn) · X.T (d, bn)ᵀ`` is the MXU's A·Bᵀ form. A
  row-block kernel here would make XLA copy X into ``{1,0:T(8,128)}``,
  which pads the features to 128 lanes, before every fit (PR 23 found
  it: 20 ms and 8 GiB of temporaries at 2^24 x 64).
* ``d % 128 == 0`` — **row-major**. X arrives
  ``{1,0}``, nothing is padded and no copy is made: ``(bm, d)`` row
  blocks, clusters on lanes, ``k`` rounded up to 128.

MXU dots per block (scores and update), both with f32 accumulation. The
argmin drops the ||x||^2 term (constant per row — it cannot change the
winner), so scores are just c2 - 2 x.c with the manual ``"bf16x3"`` split
product by default (HIGH-class accuracy — the guard from
``_kcluster._d2`` — via MXU-guaranteed DEFAULT-tier dots, see
pallas_util.dot_f32).

Scope: TPU f32 fits — single-device directly, multi-device via
`lloyd_fit_pallas_sharded` (shard_map over row shards + one psum of the
sums/counts per iteration, the same single-collective shape as the XLA
fit). Both forms share the kernel body (:func:`_lloyd_kernel`, which
only names its axes differently), the fits' ``while_loop``, centre
update, ``psum`` and final pass (:func:`_lloyd_loop`,
:func:`_final_pass`); they differ in the operands :func:`_lloyd_operands`
lays out and the block specs :func:`_lloyd_update` gives the
``pallas_call``. The final
labels/inertia pass stays on the XLA `_d2` form — one extra pass at the
end of the fit is noise across max_iter iterations.
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
    "lloyd_form",
    "pallas_lloyd_applicable",
]

_I0 = np.int32(0)  # i32 index-map literal (jax_enable_x64 guard)
_MAX_D = 512
_MAX_K = 1024
_BM = 512  # rows a block, row-major form
# Feature-major form: elements of the widest tile a block holds in VMEM, the
# (d, bn) block of X.T or the (kp8, bn) scores; bn is the power of two that
# fits: 8192 rows at d = 64, k = 8, fewer as either grows (2048 at k = 256),
# and a power of two so that such row counts need no pad. Found on a v5e at
# 2^24 x 64, k = 8 (PERF.md, Findings, PR 25): 9.83 ms an iteration at
# bn = 1024, 7.41 at 2048, 5.99 at 4096, 5.67 at 8192, 5.68 at 16384; the
# read of X alone takes 5.2 ms at the chip's 819 GB/s.
_FM_BLOCK = 64 * 8192


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def lloyd_form(d: int) -> str:
    """The orientation of the update's blocks for ``d`` features: the one
    in which X already lies on the chip (module docstring)."""
    return "row_major" if d % 128 == 0 else "feature_major"


def _lloyd_kernel(
    lim_ref, x_ref, c_ref, c2_ref, sums_ref, counts_ref, sums_s, counts_s,
    *, block, k, precision, feature_major,
):
    """Grid = (num_row_blocks,), sequential. Scratch (sums, counts)
    accumulates across blocks; written out at the last block. ``lim_ref``
    holds this buffer's LOCAL valid-row count — rows at or past it (the
    global tail pad on the last shards, plus any local block-size
    round-up pad) drop out of sums and counts.

    One body for both orientations: the block of X is ``(bm, d)`` with
    clusters on lanes (row-major) or ``(d, bn)`` of ``X.T`` with clusters
    on sublanes (feature-major); ``ca`` is the axis clusters lie on in the
    scores, rows lie on the other."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        sums_s[:] = jnp.zeros_like(sums_s)
        counts_s[:] = jnp.zeros_like(counts_s)

    x = x_ref[:]  # (bm, d) or (d, bn) f32
    c = c_ref[:]  # (kp, d) f32
    # ``precision`` (a tier or "bf16x3") for the scores dot is swept
    # on-chip by scripts/tpu_tune.py (Mosaic lowering cost per strategy
    # is not uniform; see pallas_util.dot_f32).
    # ||c||^2 arrives broadcast along the row axis, (8, kp) or (kp, 128):
    # reducing c*c over lanes in here leaves a sublane vector, and
    # Mosaic's relayout of it to the (1, kp) row the broadcast needs costs
    # ~64 KB of scoped VMEM per block row (32 MB at bm=512, over the
    # 16 MiB limit)
    if feature_major:
        ca = 0
        dot = dot_f32(c, x, (((1,), (0,)), ((), ())), precision)  # (kp, bn)
        c2 = c2_ref[:, 0:1]
    else:
        ca = 1
        dot = dot_f32(x, c, (((1,), (1,)), ((), ())), precision)  # (bm, kp)
        c2 = c2_ref[0:1, :]
    score = c2 - jnp.float32(2.0) * dot  # argmin-equiv. to d2
    jidx = jax.lax.broadcasted_iota(jnp.int32, score.shape, ca)
    score = jnp.where(jidx < k, score, jnp.float32(3.4e38))  # mask center pads
    # first-minimum index as two reductions over the cluster axis on
    # int32: jnp.argmin yields i64 under jax_enable_x64, which Mosaic
    # refuses
    smin = jnp.min(score, axis=ca, keepdims=True)
    labels = jnp.min(
        jnp.where(score == smin, jidx, jnp.int32(score.shape[ca])),
        axis=ca, keepdims=True,
    )  # (bm, 1) or (1, bn)
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, labels.shape, 1 - ca)
    valid = row < lim_ref[0]
    onehot = jnp.where(
        (labels == jidx) & valid, jnp.float32(1.0), jnp.float32(0.0)
    )  # as the scores
    # the update contracts the row axis of both (feature-major: the lane
    # dimension, the MXU's A.B^T form, no transpose). It carries the same
    # guard: onehot is exact in bf16, so the split product recovers
    # f32-class center sums — a bare DEFAULT dot would bake ~2^-9 operand
    # rounding into every center coordinate
    sums_s[:] += dot_f32(
        onehot, x, (((1 - ca,), (1 - ca,)), ((), ())), precision
    )  # (kp, d)
    counts_s[:] += jnp.broadcast_to(
        jnp.sum(onehot, axis=1 - ca, keepdims=True), counts_s.shape
    )

    @pl.when(i == nb - 1)
    def _flush():
        sums_ref[:] = sums_s[:]
        counts_ref[:] = counts_s[:]


def _lloyd_update(x, centers_pad, lim, k, block, feature_major, interpret,
                  precision):
    """One fused accumulation pass: (sums (kp, d), counts (kp, 1)).
    :func:`_lloyd_operands` pads both operands: row-major takes ``x`` as
    (mp, d) and ``kp % 128 == 0``, feature-major ``X.T`` as (d, mp) and
    ``kp % 8 == 0``, ``mp % block == 0`` in both. ``lim`` is the LOCAL
    valid-row count, int32 (1,)."""
    kp, d = centers_pad.shape
    c2 = jnp.sum(centers_pad * centers_pad, axis=1)
    if feature_major:
        grid = x.shape[1] // block
        x_spec = pl.BlockSpec((d, block), lambda i: (_I0, i), memory_space=pltpu.VMEM)
        aux = (kp, 128)  # clusters on sublanes, lane-broadcast
        c2 = jnp.broadcast_to(c2[:, None], aux)
    else:
        grid = x.shape[0] // block
        x_spec = pl.BlockSpec((block, d), lambda i: (i, _I0), memory_space=pltpu.VMEM)
        aux = (8, kp)  # clusters on lanes, sublane-broadcast
        c2 = jnp.broadcast_to(c2[None, :], aux)
    whole = lambda shape: pl.BlockSpec(
        shape, lambda i: (_I0, _I0), memory_space=pltpu.VMEM
    )
    sums, counts = pl.pallas_call(
        functools.partial(
            _lloyd_kernel, block=block, k=k, precision=precision,
            feature_major=feature_major,
        ),
        grid=(grid,),
        in_specs=[
            # explicit i32 index map: a bare SMEM BlockSpec synthesizes a
            # default map whose literals trace as i64 under jax_enable_x64,
            # which Mosaic cannot legalize ("func.return(i64)")
            pl.BlockSpec((1,), lambda i: (_I0,), memory_space=pltpu.SMEM),
            x_spec,
            whole((kp, d)),
            whole(aux),
        ],
        out_specs=[whole((kp, d)), whole(aux)],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct(aux, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kp, d), jnp.float32),
            pltpu.VMEM(aux, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="lloyd_update",
    )(lim, x, centers_pad, c2)
    return sums, (counts[:, 0:1] if feature_major else counts[0:1, :].T)


def _lloyd_operands(xs, centers0, block_m):
    """What :func:`_lloyd_update` takes for a local buffer ``xs`` (m, d)
    in the form of its ``d``: (x operand, padded centres, rows a block,
    feature_major). Rows are zero-padded to the block, and drop out at
    ``lim``; features never are."""
    m, d = xs.shape
    k = centers0.shape[0]
    feature_major = lloyd_form(d) == "feature_major"
    kp = _round_up(k, 8 if feature_major else 128)
    xs = xs.astype(jnp.float32)
    if feature_major:
        # a block's rows are lanes: whole tiles of 128
        fits = max(_FM_BLOCK // max(_round_up(d, 8), kp), 128)
        block = block_m or 1 << (fits.bit_length() - 1)
        block = _round_up(min(block, m), 128)
        x = jnp.pad(xs.T, ((0, 0), (0, _round_up(m, block) - m)))
    else:
        block = min(block_m or _BM, _round_up(m, 8))
        x = jnp.pad(xs, ((0, _round_up(m, block) - m), (0, 0)))
    c0 = jnp.pad(centers0.astype(jnp.float32), ((0, kp - k), (0, 0)))
    return x, c0, block, feature_major


def _lloyd_loop(update, c0, max_iter, tol):
    """The Lloyd iterations of both fits: ``update(c)`` gives the (sums
    (kp, d), counts (kp, 1)) of all rows, whichever buffers hold them."""

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(carry):
        c, it, _ = carry
        sums, cnt = update(c)  # center pads stay 0
        new_c = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c)
        shift = jnp.sum((new_c - c) ** 2)
        return new_c, it + 1, shift

    cpad, n_iter, _ = jax.lax.while_loop(
        cond, body, (c0, jnp.int32(0), jnp.asarray(jnp.inf, jnp.float32))
    )
    return cpad, n_iter


def _final_pass(xb, centers, n):
    """Final assignment on the XLA form (one pass; exact d2 for inertia)."""
    from ._kcluster import _d2

    # the whole pass after the loop: without the barrier XLA hoists the
    # part that needs no centres (||x||^2, one read of X) before the first
    # kernel, where the trace counts it as the fit's prologue
    xb, centers = jax.lax.optimization_barrier((xb, centers))
    w = (jnp.arange(xb.shape[0]) < n).astype(xb.dtype)
    d2 = _d2(xb, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    return centers, labels, inertia


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
    block_m: int | None = None,
    interpret: bool = False,
    precision: DotPrecision = "bf16x3",
):
    """The whole K-Means fit with the fused update kernel inside a
    `lax.while_loop`; returns (centers (k, d), labels (m,), inertia,
    n_iter) with the same semantics as `kmeans._lloyd_fit` (labels/inertia
    from one final XLA `_d2` pass over the converged centers).
    ``block_m`` overrides the rows a block of either form."""
    k = centers0.shape[0]
    x, c0, block, feature_major = _lloyd_operands(xb, centers0, block_m)
    lim = jnp.full((1,), n, jnp.int32)
    cpad, n_iter = _lloyd_loop(
        lambda c: _lloyd_update(
            x, c, lim, k, block, feature_major, interpret, precision),
        c0, max_iter, tol,
    )
    return *_final_pass(xb, cpad[:k].astype(xb.dtype), n), n_iter


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
    block_m: int | None = None,
    interpret: bool = False,
    precision: DotPrecision = "bf16x3",
):
    """Multi-device variant: the fused update runs per row-shard inside
    `shard_map` and one psum per iteration merges the (k, d)+(k,)
    sums/counts — the same single-collective-per-Lloyd-iteration shape as
    the XLA fit (and the reference's Allreduce, kmeans.py:73). Centers
    carry replicated through the while_loop; labels/inertia come from one
    final XLA `_d2` pass on the sharded buffer outside the shard_map."""
    k = centers0.shape[0]
    c_rows = xb.shape[0] // comm.size  # physical buffer rows divide the mesh by invariant

    def shard_fn(xs, centers0_):
        rank = comm.axis_index()
        # local valid rows: global logical rows falling inside this shard
        lim = jnp.clip(n - rank * c_rows, 0, c_rows).astype(jnp.int32).reshape((1,))
        x, c0, block, feature_major = _lloyd_operands(xs, centers0_, block_m)

        def update(c):
            sums, cnt = _lloyd_update(
                x, c, lim, k, block, feature_major, interpret, precision)
            # comm wrapper (not raw lax.psum) so the hop is visible to
            # the HLO auditor/cost model; pinned exact — centroid
            # accumulation predates the collective-precision knob and a
            # compressed wire would move the fixed point (heatlint HL002)
            return (comm.psum(sums, precision="off"),
                    comm.psum(cnt, precision="off"))

        return _lloyd_loop(update, c0, max_iter, tol)

    cpad, n_iter = jax.shard_map(
        shard_fn,
        mesh=comm.mesh,
        in_specs=(comm.spec(0, 2), comm.spec(None, 2)),
        out_specs=(comm.spec(None, 2), comm.spec(None, 0)),
        check_vma=False,
    )(xb, centers0)
    return *_final_pass(xb, cpad[:k].astype(xb.dtype), n), n_iter


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
