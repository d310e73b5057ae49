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
fit). Both forms share the kernel bodies (:func:`_nearest` scores a block
for both; :func:`_lloyd_kernel` and :func:`_assign_kernel` only name their
axes differently), the fits' ``while_loop``, centre update and ``psum``
(:func:`_lloyd_loop`); they differ in the operands :func:`_lloyd_operands`
lays out and the block specs :func:`_walk` gives the ``pallas_call``s.

The final pass (labels and inertia against the centres after the last
update) is chosen by the form too, :data:`FINAL_PASS`, and by nothing a
caller sets:

* feature-major — **one more pass of the kernel's own scores**,
  ``lloyd_assign`` (:func:`_assign_kernel`): the same operands and block
  walk as the update, no one-hot and no second product; a block's labels
  leave as a lane-dense int32 ``(1, bn)`` row (the ``(1, mp)`` result is a
  bitcast of the ``(mp,)`` the fit returns), its ``max(||x||^2 + smin, 0)``
  are summed in VMEM. X is read once more and nothing else is: at
  2^24 x 64, k = 8 on a v5e 5.82 ms where XLA's ``_d2`` pass took 15.13
  (it materialises the (n, 8) distances, reads X again for ||x||^2 and
  reduces argmin and min in passes of their own; PERF.md, Findings, PR 49).
  Distances are at the update's precision, so the labels are the last
  iteration's rule applied to the final centres. The labels stay int32
  (XLA's pass hands out ``argmin``'s int64 under ``jax_enable_x64``):
  widened inside the fit's program they cost 0.98 ms there (XLA's own
  ``X64Combine`` 0.77 of it) and the call read 182.1 ms; as a program of
  its own (0.70 ms) that ``KMeans._fit`` enqueues behind the fit they run
  while the host reads the fit's scalars back, and the call reads 181.2.
* row-major — **XLA's ``_d2`` pass** (:func:`_final_pass`). The kernel's
  labels are a ``(bm, 1)`` column there, and an ``(n, 1)`` int32 array is
  lane-padded on the chip: at 2^22 x 128, k = 8 the compiled fit held 2 GiB
  of temporaries for it, as much as X, and on a v5e the pass took 12.65 ms
  by the host's clock (11.16 in blocks of 1,024 rows) where XLA's takes
  7.84 (PERF.md, Findings, PR 49). It goes when the row-major blocks score
  as ``C . X_blk^T`` (clusters on sublanes, labels on lanes).
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
    "FINAL_PASS",
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


# What forms a fit's labels and inertia, by the form of its ``d`` and by
# nothing else (module docstring, "The final pass").
FINAL_PASS = {"feature_major": "kernel", "row_major": "xla"}


def _nearest(x_ref, c_ref, c2_ref, k, precision, feature_major):
    """What both kernel bodies do with a block before they part: the scores
    of its rows against the centres, their minimum and the index of the
    first minimum. Returns ``(x, ca, jidx, smin, labels)``.

    One body for both orientations: the block of X is ``(bm, d)`` with
    clusters on lanes (row-major) or ``(d, bn)`` of ``X.T`` with clusters
    on sublanes (feature-major); ``ca`` is the axis clusters lie on in the
    scores (and features in the block), rows lie on the other."""
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
    return x, ca, jidx, smin, labels


def _valid_rows(lim_ref, block, shape, ca):
    """Which rows of this block lie before ``lim``, in ``shape``."""
    row = pl.program_id(0) * block + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - ca)
    return row < lim_ref[0]


def _lloyd_kernel(
    lim_ref, x_ref, c_ref, c2_ref, sums_ref, counts_ref, sums_s, counts_s,
    *, block, k, precision, feature_major,
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

    x, ca, jidx, _, labels = _nearest(
        x_ref, c_ref, c2_ref, k, precision, feature_major)
    valid = _valid_rows(lim_ref, block, labels.shape, ca)
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


def _assign_kernel(
    lim_ref, x_ref, c_ref, c2_ref, labels_ref, inertia_ref, inertia_s,
    *, block, k, precision, feature_major,
):
    """The final assignment on the grid and operands of
    :func:`_lloyd_kernel`: a block's labels go out as they are formed
    (int32; rows past ``lim`` get one too, the caller slices them off), and
    its squared distances ``max(||x||^2 + smin, 0)`` of the rows before
    ``lim`` are summed a row position of the block at a time in scratch
    (2^24 terms of ~64 overrun one float32 scalar's digits) and reduced
    once, at the last block. No one-hot, no second product."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        inertia_s[:] = jnp.zeros_like(inertia_s)

    x, ca, _, smin, labels = _nearest(
        x_ref, c_ref, c2_ref, k, precision, feature_major)
    labels_ref[:] = labels
    x2 = jnp.sum(x * x, axis=ca, keepdims=True)  # as the labels
    inertia_s[:] += jnp.where(
        _valid_rows(lim_ref, block, labels.shape, ca),
        jnp.maximum(x2 + smin, jnp.float32(0.0)), jnp.float32(0.0),
    )

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        inertia_ref[:] = jnp.broadcast_to(
            jnp.sum(inertia_s[:], keepdims=True), inertia_ref.shape)


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (_I0, _I0), memory_space=pltpu.VMEM)


def _walk(x, centers_pad, block, feature_major):
    """The block walk both passes share: (grid, in_specs for ``lim``, x,
    the centres and ||c||^2, that ||c||^2 broadcast along the row axis)."""
    kp, d = centers_pad.shape
    c2 = jnp.sum(centers_pad * centers_pad, axis=1)
    if feature_major:
        grid = x.shape[1] // block
        x_spec = pl.BlockSpec((d, block), lambda i: (_I0, i), memory_space=pltpu.VMEM)
        c2 = jnp.broadcast_to(c2[:, None], (kp, 128))  # clusters on sublanes
    else:
        grid = x.shape[0] // block
        x_spec = pl.BlockSpec((block, d), lambda i: (i, _I0), memory_space=pltpu.VMEM)
        c2 = jnp.broadcast_to(c2[None, :], (8, kp))  # clusters on lanes
    in_specs = [
        # explicit i32 index map: a bare SMEM BlockSpec synthesizes a
        # default map whose literals trace as i64 under jax_enable_x64,
        # which Mosaic cannot legalize ("func.return(i64)")
        pl.BlockSpec((1,), lambda i: (_I0,), memory_space=pltpu.SMEM),
        x_spec,
        _whole((kp, d)),
        _whole(c2.shape),
    ]
    return grid, in_specs, c2


def _lloyd_update(x, centers_pad, lim, k, block, feature_major, interpret,
                  precision):
    """One fused accumulation pass: (sums (kp, d), counts (kp, 1)).
    :func:`_lloyd_operands` pads both operands: row-major takes ``x`` as
    (mp, d) and ``kp % 128 == 0``, feature-major ``X.T`` as (d, mp) and
    ``kp % 8 == 0``, ``mp % block == 0`` in both. ``lim`` is the LOCAL
    valid-row count, int32 (1,)."""
    kp, d = centers_pad.shape
    grid, in_specs, c2 = _walk(x, centers_pad, block, feature_major)
    aux = c2.shape
    sums, counts = pl.pallas_call(
        functools.partial(
            _lloyd_kernel, block=block, k=k, precision=precision,
            feature_major=feature_major,
        ),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_whole((kp, d)), _whole(aux)],
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


def _lloyd_assign(x, centers_pad, lim, k, block, interpret, precision):
    """The final pass over the feature-major operands of
    :func:`_lloyd_update`: (labels (mp,) int32, inertia of the rows before
    ``lim``, float32). Labels leave a block as it holds them, a lane-dense
    (1, bn) row of a (1, mp) result; the body would serve row blocks too,
    but their labels are a column (:data:`FINAL_PASS`)."""
    grid, in_specs, c2 = _walk(x, centers_pad, block, True)
    labels, inertia = pl.pallas_call(
        functools.partial(
            _assign_kernel, block=block, k=k, precision=precision,
            feature_major=True,
        ),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (_I0, i), memory_space=pltpu.VMEM),
            _whole((8, 128)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, x.shape[1]), jnp.int32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="lloyd_assign",
    )(lim, x, centers_pad, c2)
    return labels.reshape(-1), inertia[0, 0]


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
    """The final assignment on the XLA form: (labels, inertia) of ``xb``'s
    rows before ``n``."""
    from ._kcluster import _pad_weights
    from .kmeans import _lloyd_final

    # the whole pass after the loop: without the barrier XLA hoists the
    # part that needs no centres (||x||^2, one read of X) before the first
    # kernel, where the trace counts it as the fit's prologue
    xb, centers = jax.lax.optimization_barrier((xb, centers))
    return _lloyd_final.__wrapped__(xb, _pad_weights(xb, n), centers)


def _labels_inertia(xs, x, cpad, passes):
    """Labels and inertia of the buffer ``xs`` against the fitted centres,
    by :data:`FINAL_PASS` of its form; ``x`` and ``passes`` are what the
    update took beside the form."""
    if FINAL_PASS[lloyd_form(xs.shape[1])] == "kernel":
        labels, inertia = _lloyd_assign(x, cpad, **passes)
        return labels[: xs.shape[0]], inertia
    return _final_pass(xs, cpad[: passes["k"]].astype(xs.dtype), passes["lim"][0])


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
    n_iter) with the same semantics as `kmeans._lloyd_fit`: labels (int32
    from the kernel) and float32 inertia against the centres after the last
    update, from the
    final pass of ``d``'s form (:data:`FINAL_PASS`: one more pass of the
    kernel's scores, or XLA's `_d2` pass).
    ``block_m`` overrides the rows a block of either form."""
    k = centers0.shape[0]
    x, c0, block, feature_major = _lloyd_operands(xb, centers0, block_m)
    passes = dict(
        lim=jnp.full((1,), n, jnp.int32), k=k, block=block,
        interpret=interpret, precision=precision,
    )
    cpad, n_iter = _lloyd_loop(
        lambda c: _lloyd_update(x, c, feature_major=feature_major, **passes),
        c0, max_iter, tol)
    labels, inertia = _labels_inertia(xb, x, cpad, passes)
    return cpad[:k].astype(xb.dtype), labels, inertia, n_iter


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
    carry replicated through the while_loop; the final pass runs per shard
    inside the `shard_map` too (XLA does not partition a `pallas_call`):
    labels leave split by rows, the inertia by one more psum."""
    k = centers0.shape[0]
    c_rows = xb.shape[0] // comm.size  # physical buffer rows divide the mesh by invariant

    def shard_fn(xs, centers0_):
        rank = comm.axis_index()
        x, c0, block, feature_major = _lloyd_operands(xs, centers0_, block_m)
        passes = dict(
            # local valid rows: global logical rows falling inside this shard
            lim=jnp.clip(n - rank * c_rows, 0, c_rows).astype(jnp.int32).reshape((1,)),
            k=k, block=block, interpret=interpret, precision=precision,
        )

        def update(c):
            sums, cnt = _lloyd_update(x, c, feature_major=feature_major, **passes)
            # comm wrapper (not raw lax.psum) so the hop is visible to
            # the HLO auditor/cost model; pinned exact — centroid
            # accumulation predates the collective-precision knob and a
            # compressed wire would move the fixed point (heatlint HL002)
            return (comm.psum(sums, precision="off"),
                    comm.psum(cnt, precision="off"))

        cpad, n_iter = _lloyd_loop(update, c0, max_iter, tol)
        labels, inertia = _labels_inertia(xs, x, cpad, passes)
        return (cpad[:k].astype(xs.dtype), labels,
                comm.psum(inertia, precision="off"), n_iter)

    return jax.shard_map(
        shard_fn,
        mesh=comm.mesh,
        in_specs=(comm.spec(0, 2), comm.spec(None, 2)),
        out_specs=(comm.spec(None, 2), comm.spec(0, 1), comm.spec(None, 0),
                   comm.spec(None, 0)),
        check_vma=False,
    )(xb, centers0)


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
