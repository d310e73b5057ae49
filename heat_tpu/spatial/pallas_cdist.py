"""Pallas TPU kernel for GEMM-form pairwise euclidean distances.

The XLA path (:func:`heat_tpu.spatial.distance._quadratic_euclidean`)
computes ``sqrt(max(x2 + y2 - 2 x@yT, 0))`` as a dot plus broadcast
elementwise consumers; at bench shapes (m=n=16384, k=128) the m×n f32
intermediates dominate — several extra HBM round trips over the one
obligatory output write. This kernel fuses the whole epilogue into the
GEMM's output tile while it is still in VMEM: one HBM write total (the
r4 bench measured 7.2 TF/s counted on the XLA path; the output-bandwidth
roofline at these shapes permits ~30-50 TF/s).

The kernel writes the (m, n) result at its own shape. The grid is
``cdiv`` over (block_m, block_n) blocks, so where m or n is no block
multiple the last block of that axis is ragged: Mosaic drops what it
writes past the array, and no padded output, slice or other m×n pass
follows the kernel. Only the inputs are zero-padded to block multiples
(m×k and n×k, small beside the result), so the discarded lanes of an edge
block are computed from zeros and never from undefined memory.

Epilogues: ``dist`` (euclidean distance, the cdist result) and ``rbf``
(``exp(-gamma * d2)`` — the Gaussian kernel matrix directly, saving the
separate exp pass that :func:`heat_tpu.spatial.rbf` otherwise runs).

The in-kernel dot defaults to the manual ``"bf16x3"`` split product
(pallas_util.dot_f32) — HIGH-class accuracy, the documented guard against
catastrophic cancellation on the cdist(X, X) diagonal (distance.py:36-39),
from three DEFAULT-tier dots that provably land on the MXU.

Scope gate: f32 tiles with k ≤ 512 (the small-k regime where the epilogue
dominates; larger k is GEMM-bound and XLA's path is already fine — and
blocks must fit VMEM).

No reference analog (the reference's distance engine is ring-MPI torch,
distance.py:209); this is TPU-native plumbing under the same API.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.pallas_util import DotPrecision, dot_f32
from heat_tpu import _knobs as knobs

__all__ = ["euclid_pallas", "pallas_cdist_applicable", "cdist_precision"]

# jax_enable_x64 is on framework-wide: pin index-map literals to i32 (a
# Python-int 0 would trace as i64, which Mosaic cannot legalize — same
# guard as pallas_attention._I0)
_I0 = np.int32(0)

_MAX_K = 512  # f32 (bm, kp)+(bn, kp) tiles must fit VMEM; beyond this the
# workload is GEMM-bound and the XLA path is the right tool

# In-kernel dot strategy override. The "bf16x3" default is analysis-backed
# but UNMEASURED on hardware (advisor r5); until the scripts/tpu_tune.py
# sweep lands on-chip numbers, this env var is the one-line revert knob —
# no source edit, no redeploy (docs/TUNING_RUNBOOK.md).
_PREC_ENV = "HEAT_TPU_CDIST_PREC"
_PREC_VALUES = ("bf16x3", "default", "high", "highest")


def cdist_precision() -> DotPrecision:
    """The in-kernel dot strategy for the fused cdist kernel: ``"bf16x3"``
    unless ``HEAT_TPU_CDIST_PREC`` names one of ``bf16x3`` / ``default`` /
    ``high`` / ``highest`` (the ``jax.lax.Precision`` tiers). Read at call
    time, so a sweep can flip it between runs of one process."""
    v = (knobs.raw(_PREC_ENV, "") or "").strip().lower()
    if not v or v == "bf16x3":
        return "bf16x3"
    if v in _PREC_VALUES:
        return v.upper()  # dot_f32 resolves tier names via lax.Precision
    warnings.warn(
        f"{_PREC_ENV}={v!r} is not one of {_PREC_VALUES}; "
        "keeping the bf16x3 default"
    )
    return "bf16x3"


def _kernel(gamma_ref, x_ref, y_ref, o_ref, *, epilogue, precision):
    xb = x_ref[:]  # (bm, kp) f32
    yb = y_ref[:]  # (bn, kp) f32
    # contraction over k with f32 accumulation. ``precision`` is a
    # lax.Precision tier or "bf16x3" (manual MXU-guaranteed three-pass
    # split product, pallas_util.dot_f32) — HIGH-class accuracy is the
    # XLA path's documented cancellation guard (distance.py:36-39);
    # which strategy is fastest is measured by scripts/tpu_tune.py
    dot = dot_f32(xb, yb, (((1,), (1,)), ((), ())), precision)
    x2 = jnp.sum(xb * xb, axis=1, keepdims=True)  # (bm, 1)
    y2 = jnp.sum(yb * yb, axis=1)[None, :]  # (1, bn)
    d2 = jnp.maximum(x2 + y2 - jnp.float32(2.0) * dot, jnp.float32(0.0))
    if epilogue == "rbf":
        o_ref[:] = jnp.exp(-gamma_ref[0, 0] * d2)
    else:
        o_ref[:] = jnp.sqrt(d2)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def euclid_pallas(
    x: jax.Array,
    y: jax.Array,
    gamma=0.0,
    *,
    epilogue: str = "dist",
    block_m: int = 512,
    block_n: int = 1024,
    interpret: bool = False,
    precision: Optional[DotPrecision] = None,
) -> jax.Array:
    """Fused pairwise euclidean kernel on one device's tiles.

    ``x`` (m, k) and ``y`` (n, k) f32; returns (m, n) f32 — the distance
    matrix (``epilogue='dist'``) or Gaussian kernel matrix
    (``epilogue='rbf'`` with ``gamma``). Inputs are zero-padded to block
    multiples (zero feature columns contribute nothing to dot or norms);
    the result is written at (m, n) itself, its edge blocks ragged, so pad
    rows never reach it.

    ``precision=None`` (the default) resolves :func:`cdist_precision` —
    ``"bf16x3"`` unless the ``HEAT_TPU_CDIST_PREC`` env override names a
    ``jax.lax.Precision`` tier.
    """
    if precision is None:
        precision = cdist_precision()
    return _euclid_pallas_jit(
        x, y, gamma, epilogue=epilogue, block_m=block_m, block_n=block_n,
        interpret=interpret, precision=precision,
    )


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "block_m", "block_n", "interpret", "precision"),
)
def _euclid_pallas_jit(
    x: jax.Array,
    y: jax.Array,
    gamma=0.0,
    *,
    epilogue: str = "dist",
    block_m: int = 512,
    block_n: int = 1024,
    interpret: bool = False,
    precision: DotPrecision = "bf16x3",
) -> jax.Array:
    m, k = x.shape
    n = y.shape[0]
    bm, bn = min(block_m, _round_up(m, 8)), min(block_n, _round_up(n, 128))
    # feature lanes pad at 64-granularity (k=64/128 stay unpadded)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, 64)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (np_, kp) != (n, k):
        y = jnp.pad(y, ((0, np_ - n), (0, kp - k)))
    gamma_arr = jnp.asarray(gamma, jnp.float32).reshape(1, 1)

    # out_shape is (m, n) itself: a ragged last block's writes past the
    # array are dropped, and the padded inputs feed those lanes zeros
    return pl.pallas_call(
        functools.partial(_kernel, epilogue=epilogue, precision=precision),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (_I0, _I0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, kp), lambda i, j: (i, _I0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, kp), lambda i, j: (j, _I0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="euclid_tile",
    )(gamma_arr, x.astype(jnp.float32), y.astype(jnp.float32))


def pallas_cdist_applicable(k: int, jnp_dtype) -> bool:
    """Whether the fused kernel covers this (k, dtype) on the current
    default backend (TPU only — interpret mode off-TPU would be a de-opt)."""
    return (
        jax.default_backend() == "tpu"
        and k <= _MAX_K
        and jnp_dtype == jnp.float32
    )
