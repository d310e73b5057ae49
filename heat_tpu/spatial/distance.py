"""Pairwise distance computations.

Re-design of reference heat/spatial/distance.py:136-494, whose engine
`_dist` (:209) is the reference's ring-communication showpiece: each rank
keeps a stationary row block and circulates moving blocks rank→rank+1 with
Send/Recv (:280-326), exploiting symmetry by shipping computed tiles back.
Here a distance matrix has two launches:

* **Local program (default)**: one jitted XLA program, `_local_dist`, on
  every backend, mesh, feature count and dtype. x keeps its rows (split 0
  or whole), y is whole on every chip, and each chip writes its slab of
  the result with no collective; the `rbf` epilogue is part of the same
  program. With ``quadratic_expansion`` the block is the GEMM form
  ``‖a−b‖² = ‖a‖² + ‖b‖² − 2 a·bᵀ``, which is the benchmark path
  (`cdist-susy-1chip`): one output fusion, bound by the write of the result.
* **Ring program** (`ring=True`, both operands row-split): a `shard_map`
  kernel with the reference's schedule — stationary local rows, K-side
  blocks circulated with `jax.lax.ppermute` over ICI, `lax.fori_loop` over
  mesh steps. Same schedule as ring attention (SURVEY §5); peak memory per
  chip drops from O(n·m) to O(n·m/p).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import program_cache, types
from ..core.dndarray import DNDarray
from .. import telemetry

__all__ = ["cdist", "manhattan", "rbf"]


def _quadratic_euclidean(x: jax.Array, y: jax.Array) -> jax.Array:
    """‖x_i − y_j‖ via the GEMM form, clamped for numerical safety.

    The GEMM runs at HIGH precision (bf16x3): on TPU the default bf16 passes
    lose ~1e-3 relative, which catastrophic cancellation at small distances
    (e.g. the cdist(X, X) diagonal) turns into absolute errors of ~0.3."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True).T
    d2 = x2 + y2 - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGH)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def _pairwise_euclidean(x: jax.Array, y: jax.Array) -> jax.Array:
    diff = x[:, None, :] - y[None, :, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


def _pairwise_manhattan(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _blocked_rows(fn, x: jax.Array, y: jax.Array, budget_bytes: Optional[int] = None) -> jax.Array:
    """Apply a pairwise *broadcast-form* block fn over row blocks of ``x`` so
    the (block, n, k) broadcast temporary stays under ``budget_bytes`` (the
    reference streams blocks rank-to-rank for the same reason,
    distance.py:280-326; single-chip the stream becomes a `lax.map` over row
    tiles). GEMM-form fns need no blocking — call them directly.

    The default budget is 256 MiB, shrunk by the resilience memory guard
    when ``HEAT_TPU_HBM_BUDGET`` is set (ISSUE 5 degradation ladder: the
    batch axis chunks to fit the declared budget instead of overflowing).
    Resolved at trace time — a program traced under one budget keeps its
    block size until the avals change."""
    if budget_bytes is None:
        from ..resilience import memory_guard

        budget_bytes = memory_guard.temp_budget(1 << 28)
    m, k = x.shape
    n = y.shape[0]
    per_row = max(1, n * k * x.dtype.itemsize)
    bs = max(1, min(m, budget_bytes // per_row))
    if bs >= m:
        return fn(x, y)
    nb = -(-m // bs)
    xp = jnp.pad(x, ((0, nb * bs - m), (0, 0)))
    out = jax.lax.map(lambda xb: fn(xb, y), xp.reshape(nb, bs, k))
    return out.reshape(nb * bs, n)[:m]


# Stable module-level block fns (identity-stable so the jit cache below hits).
_blocked_euclidean = partial(_blocked_rows, _pairwise_euclidean)
_blocked_manhattan = partial(_blocked_rows, _pairwise_manhattan)


@partial(jax.jit, static_argnums=(0, 3))
def _local_dist(block_fn, xm: jax.Array, ym: jax.Array, dt, gamma=None) -> jax.Array:
    """The local distance program: cast, block fn and, where ``gamma`` is
    given, the Gaussian-kernel epilogue, compiled as one XLA program (the
    epilogue fuses into the write of the result: no second m×n pass).
    ``gamma`` is traced, so one program serves every ``sigma``; its absence
    is static. On a mesh the program partitions by its operands' shardings
    (x split 0, y whole on every chip): each chip writes its slab and no
    collective runs."""
    d = block_fn(xm.astype(dt), ym.astype(dt))
    return d if gamma is None else jnp.exp(-gamma * d * d)


@jax.jit
def _rbf_from_dist(d: jax.Array, gamma) -> jax.Array:
    """The Gaussian-kernel epilogue of the ring launch, over its result."""
    return jnp.exp(-gamma * d * d)


def _ring_dist(
    x: DNDarray, y: DNDarray, block_fn: Callable, audit_cost=None
) -> jax.Array:
    """Ring-pipelined block distance matrix (reference distance.py:280-326).

    Both operands row-split. Each mesh position keeps its stationary x-block
    and circulates the y-block one hop per step; after p steps every position
    has filled its (local rows × all columns) slab. ``audit_cost`` (an
    analytic CollectiveCost) turns on the HLO collective audit of the
    kernel program (telemetry/hlo.py).

    Schedule (ISSUE 6): by default the loop body is **double-buffered** —
    the next hop's ppermute is issued *before* the current block's tile
    GEMM, so the permute carries no data dependence on the compute and
    XLA's latency-hiding scheduler can overlap the two — and the final
    dead hop (which only returns each block home) is peeled off, so the
    ring runs ``p-1`` hops instead of ``p``. Tile values and update
    order are untouched: the result is bit-identical to the serial
    schedule, which ``HEAT_TPU_RING_OVERLAP=0`` restores verbatim
    (core/relayout_planner.py `ring_overlap`)."""
    from ..core import relayout_planner

    comm = x.comm
    p = comm.size
    axis = comm.axis_name
    xm = x.larray
    ym = y.larray
    cy = ym.shape[0] // p
    n_cols = ym.shape[0]
    overlap = relayout_planner.ring_overlap() and p > 1

    def kernel(xb, yb):
        rank = jax.lax.axis_index(axis)
        out = jnp.zeros((xb.shape[0], n_cols), dtype=xb.dtype)
        # mark the accumulator as device-varying for the scan carry typing
        out = jax.lax.pcast(out, (axis,), to="varying")

        def tile_into(t, yblk, out):
            # the ring sends i→i+1, so after t hops shard i holds origin
            # (i−t) mod p
            col = ((rank - t) % p) * cy
            tile = block_fn(xb, yblk)
            zero = jnp.zeros((), dtype=col.dtype)
            return jax.lax.dynamic_update_slice(out, tile, (zero, col))

        if overlap:
            def step(t, carry):
                yblk, out = carry
                # hop FIRST (no dependence on the tile GEMM below — the
                # permute rides under the local compute), consume second
                ynext = comm.ring_permute(yblk)
                out = tile_into(t, yblk, out)
                return (ynext, out)

            yb, out = jax.lax.fori_loop(0, p - 1, step, (yb, out))
            # last block: compute only — the p-th hop of the serial
            # schedule moved data nobody consumed
            return tile_into(p - 1, yb, out)

        def step(t, carry):
            yblk, out = carry
            out = tile_into(t, yblk, out)
            # the comm wrapper (not raw lax.ppermute) so the hop is named
            # in telemetry's trace-time collective record
            yblk = comm.ring_permute(yblk)
            return (yblk, out)

        _, out = jax.lax.fori_loop(0, p, step, (yb, out))
        return out

    spec = comm.spec(0, 2)
    out_spec = spec
    # block_fn is a module-level function (stable identity), so the ring
    # program is shared across calls of the same kernel + layout family;
    # the schedule is part of the signature — serial and double-buffered
    # kernels never share a program. The collective-compression wire mode
    # (ISSUE 9 — the circulating y-block is re-quantized per hop under
    # HEAT_TPU_COLLECTIVE_PREC) is part of it too: modes key separate
    # programs and repeat dispatch per mode stays zero-recompile.
    from ..core import collective_prec

    wire = collective_prec.effective(ym.dtype)
    key = (block_fn, cy, n_cols, "overlap" if overlap else "serial", wire)
    smapped = program_cache.cached_program(
        "ring_cdist", key,
        lambda: jax.shard_map(
            kernel, mesh=comm.mesh, in_specs=(spec, spec),
            out_specs=out_spec,
        ),
        comm=comm,
    )
    if audit_cost is not None:
        # the audit lowers the SAME cached program the call executes
        telemetry.hlo.audit_call(
            "ring_cdist",
            lambda: (smapped, (xm, ym)),
            predicted=audit_cost,
            key=program_cache.program_key("ring_cdist", key, comm=comm),
            fields={"mesh": p},
        )
    return smapped(xm, ym)


def _dist(
    x: DNDarray,
    y: Optional[DNDarray],
    block_fn: Callable,
    ring_ok: bool,
    ring: bool,
    rbf_gamma: Optional[float] = None,
    audit: bool = False,
) -> DNDarray:
    """Distance engine (reference distance.py:209): result is
    (n_x, n_y) distributed along the rows of x. ``rbf_gamma`` composes the
    Gaussian-kernel epilogue: inside the local program, or over the ring
    launch's result."""
    with telemetry.span("heat_tpu.cdist"):
        return _dist_phases(x, y, block_fn, ring_ok, ring, rbf_gamma, audit)


def _dist_phases(x, y, block_fn, ring_ok, ring, rbf_gamma, audit) -> DNDarray:
    """:func:`_dist` under its span; prepare, launch and wrap tile it."""
    with telemetry.span("heat_tpu.cdist.prepare"):
        if not isinstance(x, DNDarray):
            raise TypeError(f"x must be a DNDarray, but was {type(x)}")
        if x.ndim != 2:
            raise NotImplementedError(f"x has {x.ndim} dimensions, expecting 2")
        if y is None:
            y = x
        if not isinstance(y, DNDarray):
            raise TypeError(f"y must be a DNDarray, but was {type(y)}")
        if y.ndim != 2:
            raise NotImplementedError(f"y has {y.ndim} dimensions, expecting 2")
        if x.shape[1] != y.shape[1]:
            raise ValueError(
                f"inputs must have the same number of features, got {x.shape[1]} and {y.shape[1]}"
            )
        if x.split is not None and x.split != 0:
            raise NotImplementedError("cdist requires x.split in (None, 0)")

        promoted = types.promote_types(types.promote_types(x.dtype, y.dtype), types.float32)
        jt = promoted.jnp_type()
        out_split = 0 if x.split == 0 else None
        m, n = x.shape[0], y.shape[0]
        # a host scalar: the launch carries it, no program of its own
        gamma = None if rbf_gamma is None else np.asarray(rbf_gamma, jt)

        use_ring = (
            ring
            and ring_ok
            and x.split == 0
            and y.split == 0
            and x.comm.size > 1
        )
        if use_ring:
            # ring kernel works on the padded buffers; x pad rows land in
            # output pad rows, y pad columns are sliced off below. The hop
            # count is schedule-dependent: the double-buffered kernel skips
            # the final dead hop (p-1 hops), the serial kernel permutes p
            # times.
            from ..core import collective_prec, relayout_planner

            p_ring = x.comm.size
            hops = p_ring - 1 if relayout_planner.ring_overlap() else p_ring
            ring_wire = collective_prec.effective(jt)
            cost, fields, do_audit = telemetry.op_cost(
                telemetry.collectives.ring_cdist_cost, n, x.shape[1],
                promoted.byte_size(), x.comm.size, hops, ring_wire,
                collective_prec.block_size(), audit=audit,
            )
        else:
            # y's logical rows become output COLUMNS, whole on every
            # row-shard (the replicated-centers pattern): replicate via the
            # compiled relayout when y is split — multi-host safe, unlike
            # the host-logical view
            xa = x.larray
            yb = y._relayout(None) if y.split is not None else y.larray

    with telemetry.span("heat_tpu.cdist.launch"):
        if use_ring:
            with telemetry.span(
                "ring_cdist", gshape=[m, n], mesh=x.comm.size,
                overlap=hops < p_ring, **fields
            ) as sp:
                xm = x._masked(0).astype(jt)
                ym = y._masked(0).astype(jt)
                xw = DNDarray(xm, x.shape, promoted, 0, x.device, x.comm, True)
                yw = DNDarray(ym, y.shape, promoted, 0, y.device, y.comm, True)
                out = sp.output(
                    _ring_dist(
                        xw, yw, block_fn,
                        audit_cost=cost if do_audit else None,
                    )
                )
        else:
            out = _local_dist(block_fn, xa, yb, jt, gamma)

    with telemetry.span("heat_tpu.cdist.wrap"):
        if use_ring:
            out = out[:, :n]
            if gamma is not None:
                out = _rbf_from_dist(out, gamma)
        return DNDarray(out, (m, n), promoted, out_split, x.device, x.comm, True)


def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False, ring: bool = False, audit: bool = False) -> DNDarray:
    """Euclidean distance matrix (reference distance.py:136).

    ``quadratic_expansion`` selects the GEMM form (reference offers the same
    switch); ``ring=True`` (extension) forces the ppermute ring kernel for
    O(n·m/p) per-chip memory when both operands are row-split.
    ``audit=True`` (or ``HEAT_TPU_HLO_AUDIT=1``) lower-compiles the ring
    kernel and diffs the collectives XLA actually emitted against the
    analytic cost model (telemetry/hlo.py)."""
    fn = _quadratic_euclidean if quadratic_expansion else _blocked_euclidean
    return _dist(X, Y, fn, ring_ok=True, ring=ring, audit=audit)


def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False, ring: bool = False, audit: bool = False) -> DNDarray:
    """City-block distance matrix (reference distance.py:186)."""
    return _dist(X, Y, _blocked_manhattan, ring_ok=True, ring=ring, audit=audit)


def rbf(
    X: DNDarray,
    Y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
    ring: bool = False,
    audit: bool = False,
) -> DNDarray:
    """Gaussian kernel matrix exp(−‖x−y‖²/2σ²) (reference distance.py:159).

    The exp epilogue is part of the local distance program (`_local_dist`:
    it fuses into the write of the result, no separate m×n pass); only the
    ring launch (``ring=True``) applies it as a pass over its result."""
    gamma = 1.0 / (2.0 * sigma * sigma)
    fn = _quadratic_euclidean if quadratic_expansion else _blocked_euclidean
    return _dist(X, Y, fn, ring_ok=True, ring=ring, rbf_gamma=gamma,
                 audit=audit)
