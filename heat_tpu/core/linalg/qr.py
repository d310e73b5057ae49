"""Distributed QR decomposition.

Re-design of reference heat/core/linalg/qr.py:17-1018, which implements a
tiled CAQR over `SquareDiagTiles` with hand-written Householder merges and
Bcasts of local Q blocks (after Zheng+2018 / Hadri+2010). On TPU the
row-split case is the classic **TSQR** (communication-avoiding QR) expressed
as a `shard_map`: local QR per shard, all-gather of the small R factors, a
redundant replicated QR of the stacked Rs, and one local GEMM to update Q —
two MXU GEMM stages and a single ICI all-gather instead of the reference's
O(tiles²) message choreography.

The column-split case (reference qr.py:849-1018, a per-tile-column loop of
local QRs + Bcasts) is re-designed as **CholeskyQR2** over two shard_map
kernels: a ring Gram kernel building ``G = AᵀA`` tile-by-tile (the cdist
ring schedule — no device ever holds more than one circulating block), a
replicated Cholesky of the small ``G``, and a `psum_scatter` panel solve
``Q = A·R⁻¹`` that returns column-sharded Q directly. One refinement pass
restores orthogonality to ~machine eps for κ(A) up to ~1/√eps; if the first
Cholesky breaks down, a shifted Cholesky (Fukaya et al. 2020) plus an extra
refinement pass extends the reach. The matrix is never gathered — per-device
peak memory is the local block plus one circulating block.
"""

from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import program_cache, types
from ..dndarray import DNDarray
from ... import telemetry

__all__ = ["qr"]

QR = collections.namedtuple("QR", "Q, R")


def _gram_ring(buf: jax.Array, comm, audit_cost=None) -> jax.Array:
    """``G = AᵀA`` for a column-sharded (pad-zeroed) physical buffer
    ``(m, n_phys)``; returns G ``(n_phys, n_phys)`` replicated.

    Ring schedule: device i keeps its transposed block stationary, the
    blocks circulate; step t computes tile ``G[my cols, origin's cols]``.
    p steps × one (c, m)·(m, c) MXU GEMM each; comm = m·n around the ring
    plus the final n² all-gather of row blocks. ``audit_cost`` (an
    analytic CollectiveCost) turns on the HLO collective audit of the
    kernel program (telemetry/hlo.py)."""
    from .. import relayout_planner

    p = comm.size
    axis = comm.axis_name
    n_phys = buf.shape[1]
    c = n_phys // p  # per-device column-block width (used by the tile writes)
    # double-buffered overlap schedule (ISSUE 6): hop before the tile
    # GEMM (so the permute rides under the compute) and peel the final
    # dead hop — p-1 hops, bit-identical tiles/updates; the serial p-hop
    # kernel is restored by HEAT_TPU_RING_OVERLAP=0
    overlap = relayout_planner.ring_overlap() and p > 1

    xt = buf.T  # (n_phys, m) split=0 — local transpose, no relayout

    def kernel(xt_blk):
        rank = jax.lax.axis_index(axis)

        def tile_into(t, circ, acc):
            origin = (rank - t) % p
            tile = xt_blk @ circ.T  # (c, c)
            return jax.lax.dynamic_update_slice(
                acc, tile, (jnp.int32(0), (origin * c).astype(jnp.int32))
            )

        acc0 = jax.lax.pcast(
            jnp.zeros((xt_blk.shape[0], n_phys), dtype=buf.dtype),
            axis,
            to="varying",
        )
        if overlap:
            def body(t, carry):
                circ, acc = carry
                # the Gram/Cholesky factorization amplifies wire error
                # quadratically — the QR rings never compress
                cnext = comm.ring_permute(circ, precision="off")
                acc = tile_into(t, circ, acc)
                return cnext, acc

            circ, acc = jax.lax.fori_loop(0, p - 1, body, (xt_blk, acc0))
            acc = tile_into(p - 1, circ, acc)
        else:
            def body(t, carry):
                circ, acc = carry
                acc = tile_into(t, circ, acc)
                # the comm wrapper (not raw lax.ppermute) so the hop is
                # named in telemetry's trace-time collective record
                circ = comm.ring_permute(circ, precision="off")
                return circ, acc

            _, acc = jax.lax.fori_loop(0, p, body, (xt_blk, acc0))
        return jax.lax.all_gather(acc, axis, tiled=True)  # replicated G

    key = (tuple(buf.shape), str(buf.dtype),
           "overlap" if overlap else "serial")
    smapped = program_cache.cached_program(
        "cholqr_gram_ring", key,
        lambda: jax.shard_map(
            kernel,
            mesh=comm.mesh,
            in_specs=comm.spec(0, 2),
            out_specs=jax.sharding.PartitionSpec(),
            # the tiled all_gather makes the output bitwise-identical on
            # every device, but the varying-axis type system can't infer
            # that through the fori_loop carry
            check_vma=False,
        ),
        comm=comm,
    )
    if audit_cost is not None:
        # the audit lowers the SAME cached program the call executes —
        # one signature shared between registry and auditor memo
        telemetry.hlo.audit_call(
            "cholqr_gram_ring",
            lambda: (smapped, (xt,)),
            predicted=audit_cost,
            key=program_cache.program_key("cholqr_gram_ring", key, comm=comm),
            fields={"gshape": [int(buf.shape[0]), int(buf.shape[1])],
                    "mesh": p},
        )
    return smapped(xt)


def _panel_solve(buf: jax.Array, rinv_pad: jax.Array, comm) -> jax.Array:
    """``Q = A @ R⁻¹`` for column-sharded ``A`` ``(m, n_phys)`` with the
    contraction over the split axis: each device computes its partial
    ``A_local @ R⁻¹[local rows, :]`` and a `psum_scatter` along columns
    returns Q column-sharded — the result never materializes unsharded."""
    axis = comm.axis_name

    def kernel(x, rv):
        partial = x @ rv  # (m, n_phys)
        return jax.lax.psum_scatter(
            partial, axis, scatter_dimension=1, tiled=True
        )  # (m, c)

    smapped = program_cache.cached_program(
        "cholqr_panel_solve", (),
        lambda: jax.shard_map(
            kernel,
            mesh=comm.mesh,
            in_specs=(comm.spec(1, 2), comm.spec(0, 2)),
            out_specs=comm.spec(1, 2),
        ),
        comm=comm,
    )
    return smapped(buf, rinv_pad)


def _cholqr_split1(a: DNDarray, dt, calc_q: bool, audit: bool = False) -> QR:
    """CholeskyQR2 (+ shifted-Cholesky fallback) for tall column-split
    matrices; see module docstring."""
    comm = a.comm
    m, n = a.shape
    n_phys = comm.padded_size(n)
    buf = a._masked(0).astype(dt.jnp_type())  # (m, n_phys), pad cols zeroed

    eye = jnp.eye(n, dtype=buf.dtype)
    eps = float(jnp.finfo(buf.dtype).eps)
    r_factors = []
    passes_left = 2
    shifted = False
    q_buf = buf
    from .. import relayout_planner

    gram_hops = (
        comm.size - 1 if relayout_planner.ring_overlap() and comm.size > 1
        else comm.size
    )
    while passes_left > 0:
        cost, _, do_audit = telemetry.op_cost(
            telemetry.collectives.gram_ring_cost, m, n, dt.byte_size(),
            comm.size, gram_hops, audit=audit,
        )
        g = _gram_ring(q_buf, comm, audit_cost=cost if do_audit else None)[:n, :n]
        ell = jnp.linalg.cholesky(g)
        # breakdown check on the small factor (one n² host fetch): NaNs or a
        # collapsed diagonal mean G is (numerically) singular on THIS pass —
        # exactly rank-deficient inputs break the refinement pass too, since
        # their deficient Q columns come out zero
        ell_h = np.asarray(ell)
        diag = np.abs(np.diagonal(ell_h))
        if np.isnan(ell_h).any() or diag.min() <= n * eps * max(diag.max(), 1.0):
            # shifted Cholesky (Fukaya et al. 2020): guarantees the
            # factorization exists; an extra refinement pass restores
            # orthogonality of the non-deficient directions
            shift = 11.0 * eps * (m * n + n * (n + 1)) * jnp.trace(g)
            ell = jnp.linalg.cholesky(g + shift * eye)
            if not shifted:
                shifted = True
                passes_left += 1
        linv = jax.scipy.linalg.solve_triangular(ell, eye, lower=True)
        rinv = linv.T  # R = Lᵀ, so R⁻¹ = (L⁻¹)ᵀ
        rinv_pad = jnp.zeros((n_phys, n_phys), dtype=buf.dtype)
        rinv_pad = rinv_pad.at[:n, :n].set(rinv)
        q_buf = _panel_solve(q_buf, rinv_pad, comm)
        r_factors.append(ell.T)
        passes_left -= 1

    r_log = r_factors[0]
    for f in r_factors[1:]:
        r_log = f @ r_log
    r_ht = DNDarray.from_logical(r_log, 1, a.device, comm, dt)
    if not calc_q:
        return QR(None, r_ht)
    q_ht = DNDarray(q_buf, (m, n), dt, 1, a.device, comm, True)
    return QR(q_ht, r_ht)


def _wide_split1(a: DNDarray, dt, calc_q: bool) -> QR:
    """Reduced QR of a wide (m < n) column-split matrix without gathering:
    the Householder reflectors of a wide QR come only from the first ``m``
    columns, so ``Q`` equals the Q of ``A[:, :m]`` (the small m×m leading
    block — the only thing replicated) and ``R = Qᵀ A`` is a shard-local
    GEMM that keeps split=1."""
    comm = a.comm
    m, n = a.shape
    buf = a._masked(0).astype(dt.jnp_type())
    lead_fn = program_cache.cached_program(
        "qr_wide_lead", (m,),
        lambda: (lambda x: x[:, :m]),
        comm=comm, out_shardings=comm.replicated(),
    )
    lead = lead_fn(buf)
    q_log, _ = jnp.linalg.qr(lead)  # (m, m), computed redundantly per device
    # R = Qᵀ A: contraction over rows (not split) — local GEMMs, no comm
    r_buf = jnp.matmul(q_log.T, buf)
    r_ht = DNDarray(r_buf, (m, n), dt, 1, a.device, comm, True)
    if not calc_q:
        return QR(None, r_ht)
    q_ht = DNDarray.from_logical(q_log, 1, a.device, comm, dt)
    return QR(q_ht, r_ht)


def _local_tsqr(x: jax.Array, tiles: int):
    """Local (within-shard) blocked TSQR: split the block into ``tiles``
    row-panels, QR each, then QR the stacked R factors — the reference's
    ``tiles_per_proc`` knob (qr.py:17: SquareDiagTiles subdivides each rank)
    realized as a deeper on-chip reduction tree. Falls back to one dense QR
    when the panels would be wider than tall."""
    c, n = x.shape
    if tiles <= 1 or c % tiles != 0 or c // tiles < n:
        return jnp.linalg.qr(x)
    cb = c // tiles
    panels = x.reshape(tiles, cb, n)
    q1, r1 = jnp.linalg.qr(panels)  # batched: (t, cb, n), (t, n, n)
    q2, r = jnp.linalg.qr(r1.reshape(tiles * n, n))  # (t*n, n), (n, n)
    q2b = q2.reshape(tiles, n, n)
    q = jnp.einsum("tcn,tnk->tck", q1, q2b).reshape(c, n)
    return q, r


def qr(
    a: DNDarray,
    tiles_per_proc: int = 1,
    calc_q: bool = True,
    overwrite_a: bool = False,
    audit: bool = False,
) -> QR:
    """Reduced QR factorization ``a = Q @ R`` (reference qr.py:17).

    Row-split tall matrices (``m >= n``) run the TSQR shard_map kernel; the
    per-shard local stage honors ``tiles_per_proc`` as a blocked local TSQR
    (the reference's tile subdivision, re-expressed as an on-chip reduction
    tree). Shards shorter than ``n`` still work — the local R factors are
    ``min(chunk, n)`` tall and the replicated second-stage QR restores the
    full ``(n, n)`` R. Column-split tall matrices run CholeskyQR2 (ring
    Gram + psum_scatter panel solve — the reference's per-tile-column
    algorithm, qr.py:849-1018, re-designed; orthogonality ~eps up to
    κ(A)≈1/√eps, shifted-Cholesky fallback beyond). Column-split wide
    matrices (``m < n``) factor the m×m leading block (the only replicated
    piece) and finish with shard-local GEMMs. Replicated inputs use one XLA
    QR. Column signs of Q/R are not unique — compare ``Q @ R`` and
    ``Q.T @ Q``, as the reference tests do.

    ``audit=True`` (or the global ``HEAT_TPU_HLO_AUDIT=1``) additionally
    lower-compiles the distributed kernel (TSQR / ring Gram) and diffs
    the collectives XLA actually emitted against the analytic cost model
    (telemetry/hlo.py) — docs/OBSERVABILITY.md.
    """
    if not isinstance(a, DNDarray):
        raise TypeError(f"'a' must be a DNDarray, but was {type(a)}")
    if a.ndim != 2:
        raise ValueError(f"'a' must be 2-dimensional, but has {a.ndim} dimensions")
    if not isinstance(tiles_per_proc, int):
        raise TypeError(f"tiles_per_proc must be an int, but was {type(tiles_per_proc)}")

    m, n = a.shape
    comm = a.comm
    dt = types.promote_types(a.dtype, types.float32)
    chunk = comm.chunk_size(m)

    # TSQR path: rows sharded over the mesh, global m tall enough for a
    # reduced (m, n) -> (m, n)(n, n) factorization
    if a.split == 0 and comm.size > 1 and m >= n:
        buf = a._masked(0).astype(dt.jnp_type())  # zero pad rows: QR([A;0]) == ([Q;0], R)
        p = comm.size
        axis = comm.axis_name
        spec_row = comm.spec(0, 2)
        k1 = min(chunk, n)  # local R height

        def kernel(x):
            q1, r1 = _local_tsqr(x, tiles_per_proc)  # (c, k1), (k1, n)
            rs = jax.lax.all_gather(r1, axis, tiled=True)  # (p*k1, n)
            q2, r = jnp.linalg.qr(rs)  # (p*k1, kk), (kk, n) with kk=min(p*k1, n)
            i = jax.lax.axis_index(axis)
            q2_i = jax.lax.dynamic_slice_in_dim(q2, i * k1, k1, axis=0)  # (k1, kk)
            q_i = q1 @ q2_i  # (c, kk)
            return q_i, r

        # kk == n always: p*k1 >= min(p*chunk, p*n) >= min(m, n) = n
        cost, fields, do_audit = telemetry.op_cost(
            telemetry.collectives.tsqr_cost, m, n, dt.byte_size(), p,
            audit=audit,
        )
        key = ((m, n), str(buf.dtype), tiles_per_proc)
        smapped = program_cache.cached_program(
            "tsqr", key,
            lambda: jax.shard_map(
                kernel, mesh=comm.mesh, in_specs=spec_row,
                out_specs=(spec_row, spec_row),
            ),
            comm=comm,
        )
        if do_audit:
            telemetry.hlo.audit_call(
                "tsqr",
                lambda: (smapped, (buf,)),
                predicted=cost,
                key=program_cache.program_key("tsqr", key, comm=comm),
                fields={"gshape": [m, n], "mesh": p},
            )
        with telemetry.span("tsqr", gshape=[m, n], mesh=p, **fields) as sp:
            q_phys, r_tiled = smapped(buf)
            sp.output(q_phys)
            sp.output(r_tiled)
        r_log = r_tiled[:n]  # every shard computed the same R; take one copy
        r_ht = DNDarray.from_logical(r_log, None, a.device, comm, dt)
        if not calc_q:
            return QR(None, r_ht)
        q_ht = DNDarray(q_phys, (m, n), dt, 0, a.device, comm, True)
        return QR(q_ht, r_ht)

    # column-split path: CholeskyQR2 ring/scatter kernels (tall) or the
    # leading-block factorization (wide) — no gather, multi-host safe
    if a.split == 1 and comm.size > 1:
        if m >= n:
            return _cholqr_split1(a, dt, calc_q, audit=audit)
        return _wide_split1(a, dt, calc_q)

    # wide row-split: factor the m×m leading block (the small-dim² piece,
    # replicated via the compiled relayout), then R = QᵀA — a contraction
    # over the split rows that matmul renders as one psum. Multi-host safe.
    if a.split == 0 and comm.size > 1 and m < n:
        from .basics import matmul

        lead = a[:, :m]  # split=0 (m, m)
        q_log, _ = jnp.linalg.qr(lead._replicated().astype(dt.jnp_type()))
        qt_ht = DNDarray.from_logical(q_log.T, None, a.device, comm, dt)
        r_ht = matmul(qt_ht, a)
        if not calc_q:
            return QR(None, r_ht)
        q_ht = DNDarray.from_logical(q_log, 0, a.device, comm, dt)
        return QR(q_ht, r_ht)

    # general path: one XLA QR over the logical view (wide/replicated
    # inputs and single-position meshes; XLA gathers as needed)
    log = a._logical().astype(dt.jnp_type())
    q_log, r_log = jnp.linalg.qr(log)
    r_ht = DNDarray.from_logical(r_log, None if a.split != 1 else 1, a.device, comm, dt)
    if not calc_q:
        return QR(None, r_ht)
    q_ht = DNDarray.from_logical(q_log, a.split, a.device, comm, dt)
    return QR(q_ht, r_ht)
