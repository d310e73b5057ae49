"""K-Means clustering.

Re-design of reference heat/cluster/kmeans.py:13-139 (Lloyd iterations:
assign via cdist+argmin, masked-sum centroid update with an implicit
Allreduce, inertia convergence check). Here one Lloyd iteration is a single
jit-compiled function over the padded sharded sample buffer — the distance
matrix and the one-hot centroid update are both GEMMs on the MXU, and XLA
inserts the single cross-shard psum per iteration.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from .. import telemetry
from ..core import types
from ..core.dndarray import DNDarray
from ._kcluster import _KCluster, _d2

__all__ = ["KMeans"]


@partial(jax.jit, donate_argnums=())
def _lloyd_step(xb: jax.Array, w: jax.Array, centers: jax.Array):
    """One Lloyd iteration: assign + masked centroid update + inertia.

    All math is batched GEMM; `w` zeroes tail-pad rows out of the sums and
    counts (the reference's empty-shard neutral elements, _operations.py
    :401-410, become this weight vector)."""
    d2 = _d2(xb, centers)  # (m, k)
    labels = jnp.argmin(d2, axis=1)
    k = centers.shape[0]
    onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(xb.dtype) * w[:, None]
    counts = jnp.sum(onehot, axis=0)  # (k,)
    sums = onehot.T @ xb  # (k, d)
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
    )
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, labels, inertia, shift


def _lloyd_window(
    xb: jax.Array, w: jax.Array, centers: jax.Array, shift0, max_iter: int, tol
):
    """The traceable body of :func:`_lloyd_fit_carry` — a resumable
    window of Lloyd iterations with the convergence carry entering and
    leaving. Split out so the streaming mini-batch updater
    (:class:`heat_tpu.streaming.MiniBatchKMeans`) can compose the SAME
    window math inside its own cached program (one program per chunk
    shape) instead of re-deriving the iteration."""

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(carry):
        c, it, _ = carry
        new_c, _, _, shift = _lloyd_step.__wrapped__(xb, w, c)
        return new_c, it + 1, shift

    return jax.lax.while_loop(cond, body, (centers, jnp.int32(0), shift0))


@partial(jax.jit, static_argnames=("max_iter",))
def _lloyd_fit_carry(
    xb: jax.Array, w: jax.Array, centers: jax.Array, shift0, max_iter: int, tol
):
    """A resumable window of Lloyd iterations: same body as
    :func:`_lloyd_fit`, but the convergence carry (``shift``) enters and
    leaves the program, so the checkpoint driver can run the fit as exact
    chunks — ``k`` windows of ``checkpoint_every`` iterations apply the
    identical per-iteration math as one uninterrupted ``while_loop``
    (the resume-equivalence oracle in tests/test_resilience.py)."""
    return _lloyd_window(xb, w, centers, shift0, max_iter, tol)


@jax.jit
def _lloyd_final(xb: jax.Array, w: jax.Array, centers: jax.Array):
    """Final assignment + inertia for converged centers — the tail of
    :func:`_lloyd_fit`, shared by the checkpointed driver."""
    d2 = _d2(xb, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    return labels, inertia


@partial(jax.jit, static_argnames=("max_iter",))
def _lloyd_fit(xb: jax.Array, w: jax.Array, centers: jax.Array, max_iter: int, tol):
    """The whole Lloyd loop as one on-device `lax.while_loop` — the reference
    drives iterations from Python with a per-iteration convergence fetch
    (kmeans.py:122-135); on TPU that host sync per iteration would dominate,
    so the loop, the convergence test, and the final assignment all compile
    into a single XLA program (SURVEY §3.3)."""

    def cond(carry):
        _, it, shift = carry
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(carry):
        c, it, _ = carry
        new_c, _, _, shift = _lloyd_step.__wrapped__(xb, w, c)
        return new_c, it + 1, shift

    centers, n_iter, _ = jax.lax.while_loop(
        cond, body, (centers, jnp.int32(0), jnp.asarray(jnp.inf, xb.dtype))
    )
    d2 = _d2(xb, centers)
    labels = jnp.argmin(d2, axis=1)
    inertia = jnp.sum(jnp.min(d2, axis=1) * w)
    return centers, labels, inertia, n_iter


class KMeans(_KCluster):
    """K-Means clusterer (reference kmeans.py:13).

    Parameters
    ----------
    n_clusters : int
    init : 'random' | 'probability_based' | DNDarray
    max_iter : int
    tol : float
        Convergence threshold on the squared centroid shift.
    random_state : int, optional
    checkpoint_every : int, optional
        Opt-in resilience hook (ISSUE 5): checkpoint the fit state every
        this many Lloyd iterations via
        :func:`heat_tpu.resilience.save_checkpoint` — the fit then runs as
        exact iteration windows, so a killed run resumes at the last
        completed window with bit-identical results to an uninterrupted
        fit. Requires ``checkpoint_path``.
    checkpoint_path : str, optional
        Checkpoint directory (atomically swapped on every save).
    resume : bool
        Load ``checkpoint_path`` (when it exists and is a kmeans
        checkpoint) and continue from its iteration count instead of the
        initial centers.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_path: Optional[str] = None,
        resume: bool = False,
    ):
        super().__init__("euclidean", n_clusters, init, max_iter, tol, random_state)
        if checkpoint_every is not None:
            if checkpoint_every <= 0:
                raise ValueError(
                    f"checkpoint_every must be positive, got {checkpoint_every}"
                )
            if not checkpoint_path:
                raise ValueError("checkpoint_every requires checkpoint_path")
        elif resume:
            # resume only works through the windowed driver — ignoring the
            # flag would silently redo every completed iteration
            raise ValueError("resume=True requires checkpoint_every")
        self.checkpoint_every = checkpoint_every
        self.checkpoint_path = checkpoint_path
        self.resume = resume

    def fit(self, x: DNDarray) -> "KMeans":
        """Run Lloyd iterations to convergence (reference kmeans.py:102)."""
        with telemetry.span("heat_tpu.kmeans.fit"):
            return self._fit(x)

    def _fit(self, x: DNDarray) -> "KMeans":
        """:meth:`fit` under its span; the four phase spans tile it."""
        if not isinstance(x, DNDarray):
            raise TypeError(f"input needs to be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise ValueError("input needs to be 2D")
        from .pallas_lloyd import (
            FINAL_PASS,
            lloyd_fit_pallas,
            lloyd_fit_pallas_sharded,
            lloyd_form,
            pallas_lloyd_applicable,
        )

        with telemetry.span("heat_tpu.kmeans.fit.prepare"):
            dt, xb, w, centers = self._fit_buffers(x)
            tol = jnp.asarray(self.tol, xb.dtype)

        with telemetry.span("heat_tpu.kmeans.fit.launch"):
            assign = "xla"  # what forms labels and inertia: `_d2`, or the kernel
            if self.checkpoint_every is not None:
                # checkpointed fit: exact iteration windows (the pallas path
                # is a whole-fit program with no resumable carry, so the
                # windowed XLA driver serves this mode on every backend)
                centers, labels, inertia, n_iter = self._fit_checkpointed(
                    xb, w, centers
                )
            elif not pallas_lloyd_applicable(
                x.comm.size, x.split, x.shape[1], self.n_clusters, xb.dtype
            ):
                centers, labels, inertia, n_iter = _lloyd_fit(
                    xb, w, centers, self.max_iter, tol
                )
            else:
                # fused single-pass-over-X Lloyd update, its blocks in the
                # orientation X has on the chip (see pallas_lloyd)
                form = lloyd_form(x.shape[1])
                telemetry.get_registry().add(f"kmeans.lloyd.{form}")
                assign = FINAL_PASS[form]
                if x.comm.size > 1:
                    centers, labels, inertia, n_iter = lloyd_fit_pallas_sharded(
                        x.comm, xb, centers, x.shape[0], self.max_iter, tol
                    )
                else:
                    centers, labels, inertia, n_iter = lloyd_fit_pallas(
                        xb, centers, x.shape[0], self.max_iter, tol
                    )
            telemetry.get_registry().add(f"kmeans.assign.{assign}")
            # the kernel's labels are int32: widened here, the program is
            # enqueued behind the fit and runs while the host reads the
            # scalars back; in `wrap` it starts ~0.9 ms after the readback
            # has waited for the fit, on an idle chip (PERF.md, PR 49)
            labels = labels.astype(jnp.int64)

        with telemetry.span("heat_tpu.kmeans.fit.readback"):
            # the host waits for the device here
            self._n_iter = int(n_iter)
            self._inertia = float(inertia)

        with telemetry.span("heat_tpu.kmeans.fit.wrap"):
            self._cluster_centers = DNDarray.from_logical(
                centers, None, x.device, x.comm, dt
            )
            self._labels = DNDarray(
                labels, (x.shape[0],), types.int64,
                x.split, x.device, x.comm, True,
            )
        return self

    def _fit_checkpointed(self, xb, w, centers):
        """Drive Lloyd iterations in windows of ``checkpoint_every``,
        checkpointing (centers, iteration count, convergence carry) after
        each window. The carried ``shift`` makes the chunking exact: the
        sequence of per-iteration updates is identical to one uninterrupted
        :func:`_lloyd_fit` run, and a resumed fit continues it bit-for-bit
        (``shift`` round-trips through the manifest as a python float —
        exact for f32/f64 values)."""
        import os

        import numpy as np

        from .. import resilience

        path = self.checkpoint_path
        every = int(self.checkpoint_every)
        tol = jnp.asarray(self.tol, xb.dtype)
        it_done = 0
        shift = jnp.asarray(jnp.inf, xb.dtype)
        if self.resume and resilience.checkpoint.exists(path):
            leaves, extra = resilience.load_checkpoint(path, with_extra=True)
            if extra.get("algo") != "kmeans" or len(leaves) != 1:
                raise resilience.CheckpointError(
                    f"{path!r} is a {extra.get('algo')!r} checkpoint, not kmeans"
                )
            centers = jnp.asarray(leaves[0], dtype=xb.dtype)
            it_done = int(extra["n_iter"])
            shift = jnp.asarray(extra["shift"], xb.dtype)
        while it_done < self.max_iter and bool(shift > tol):
            window = min(every, self.max_iter - it_done)
            centers, n_it, shift = _lloyd_fit_carry(
                xb, w, centers, shift, window, tol
            )
            it_done += int(n_it)
            resilience.save_checkpoint(
                [np.asarray(centers)], path,
                extra={"algo": "kmeans", "n_iter": it_done,
                       "shift": float(shift)},
            )
        labels, inertia = _lloyd_final(xb, w, centers)
        return centers, labels, inertia, it_done
