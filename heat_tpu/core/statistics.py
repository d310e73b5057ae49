"""Statistical reductions (reference: heat/core/statistics.py, 18 exports).

The reference implements these with custom MPI reduction ops (packed
(value,index) buffers for argmin/argmax, statistics.py:1139-1207) and
hand-rolled moment merges (Welford-style combine :803-828, :1729-1758). Here
each is a masked jnp reduction; XLA derives the cross-shard combines. The
moment computations (var/skew/kurtosis) are two-pass — numerically stronger
than the reference's single-pass merge and free on TPU since the passes fuse.
"""

from __future__ import annotations

import builtins
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import types
from ._operations import binary_op, local_op, reduce_op
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "chunk_moments",
    "cov",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "nanmax",
    "nanmean",
    "nanmin",
    "nanstd",
    "nanvar",
    "percentile",
    "skew",
    "std",
    "var",
]


def _neutral_extreme(x: DNDarray, is_max: bool):
    if issubclass(x.dtype, types.integer):
        info = types.iinfo(x.dtype)
        return info.min if is_max else info.max
    return -float("inf") if is_max else float("inf")


def _arg_reduce(x: DNDarray, axis, is_max: bool, out=None, keepdims: bool = False) -> DNDarray:
    fn = jnp.argmax if is_max else jnp.argmin
    neutral = _neutral_extreme(x, is_max)
    if axis is None:
        buf = x._masked(neutral)
        flat_idx = fn(buf)
        if x.pad_count:
            coords = jnp.unravel_index(flat_idx, buf.shape)
            flat_idx = jnp.ravel_multi_index(coords, x.shape, mode="clip")
        res = flat_idx.astype(jnp.int64)
        if keepdims:
            res = jnp.reshape(res, (1,) * x.ndim)
            out_arr = DNDarray(res, (1,) * x.ndim, types.int64, None, x.device, x.comm, True)
        else:
            out_arr = DNDarray(res, (), types.int64, None, x.device, x.comm, True)
        if out is not None:
            out.larray = res.astype(out.dtype.jnp_type())
            return out
        return out_arr
    axis = sanitize_axis(x.shape, axis)
    buf = x._masked(neutral) if (x.split == axis and x.pad_count) else x.larray
    result = fn(buf, axis=axis)
    if keepdims:
        result = jnp.expand_dims(result, axis)
    split = x.split
    if split is None or split == axis:
        out_split = None if not keepdims or split == axis else split
        out_split = None
    else:
        out_split = split if keepdims else split - (1 if axis < split else 0)
    if keepdims:
        out_gshape = tuple(1 if d == axis else s for d, s in enumerate(x.shape))
    else:
        out_gshape = tuple(s for d, s in enumerate(x.shape) if d != axis)
    res = DNDarray(
        result.astype(jnp.int64), out_gshape, types.int64, out_split, x.device, x.comm, True
    )
    if out is not None:
        out.larray = res.larray.astype(out.dtype.jnp_type())
        return out
    return res


def argmax(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Index of the maximum (reference statistics.py `argmax` via custom
    MPI_ARGMAX reduction)."""
    return _arg_reduce(x, axis, True, out, keepdims)


def argmin(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Index of the minimum (reference statistics.py `argmin`)."""
    return _arg_reduce(x, axis, False, out, keepdims)


def _reduced_count(x: DNDarray, axis) -> int:
    if axis is None:
        return x.size
    if isinstance(axis, builtins.int):
        axes = (axis,)
    else:
        axes = tuple(axis)
    n = 1
    for a in axes:
        n *= x.shape[a]
    return n


def average(x: DNDarray, axis=None, weights: Optional[DNDarray] = None, returned: bool = False):
    """Weighted average (reference statistics.py `average`)."""
    if weights is None:
        avg = mean(x, axis)
        from . import factories

        n = _reduced_count(x, sanitize_axis(x.shape, axis) if axis is not None else None)
        wsum = factories.full(avg.shape if avg.ndim else (), float(n), dtype=types.float32,
                              split=avg.split if avg.ndim else None, device=x.device, comm=x.comm)
        return (avg, wsum) if returned else avg
    from . import arithmetics

    if weights.ndim == 1 and axis is not None and isinstance(axis, builtins.int):
        axis = sanitize_axis(x.shape, axis)
        if weights.shape[0] != x.shape[axis]:
            raise ValueError("Length of weights not compatible with specified axis")
        shape = [1] * x.ndim
        shape[axis] = weights.shape[0]
        if axis == x.split and x.comm.size > 1:
            # the weights run along the SPLIT axis — align them to x's
            # chunking (same extent → same tail pads) instead of
            # replicating an axis-length vector; the broadcast multiply
            # then stays shard-local
            wv = weights if weights.split == 0 else weights.resplit(0)
            w = DNDarray(
                jnp.reshape(wv.larray, [1] * axis + [wv.larray.shape[0]] + [1] * (x.ndim - axis - 1)),
                tuple(shape), wv.dtype, axis, x.device, x.comm, True,
            )
        else:
            w = DNDarray.from_logical(
                jnp.reshape(weights._logical(), shape), None, x.device, x.comm
            )
    elif weights.shape == x.shape:
        w = weights
    else:
        raise TypeError("Axis must be specified when shapes of x and weights differ")
    num = arithmetics.sum(arithmetics.mul(x, w), axis)
    den = arithmetics.sum(w, axis)
    avg = arithmetics.div(num, den)
    if returned:
        if tuple(den.shape) != tuple(avg.shape):
            # numpy contract: sum_of_weights carries the average's shape
            from . import factories

            den = arithmetics.mul(
                den,
                factories.ones(
                    avg.shape, dtype=den.dtype, split=avg.split,
                    device=x.device, comm=x.comm,
                ),
            )
        return avg, den
    return avg


def _aligned_weights_buf(x: DNDarray, weights):
    """``weights`` as a physical buffer aligned with ``x``'s shards (resplit
    if laid out differently), or None. Pads need no masking here — callers
    zero them via the validity mask."""
    if weights is None:
        return None
    if isinstance(weights, DNDarray):
        if tuple(weights.shape) != tuple(x.shape):
            raise ValueError("weights must have the same shape as the input")
        if weights.split != x.split:
            weights = weights.resplit(x.split)
        return weights.larray
    w = np.asarray(weights)
    if tuple(w.shape) != tuple(x.shape):
        raise ValueError("weights must have the same shape as the input")
    from . import factories

    # route raw arrays through the factory so they pick up x's tail padding
    # and sharding (a bare device_put of the logical shape would not divide
    # over the mesh when x is padded)
    return factories.array(w, split=x.split, device=x.device, comm=x.comm).larray


def _valid_weights(x: DNDarray, wbuf):
    """Per-element weights over the PHYSICAL shape: the given weights (or 1)
    at logical positions, 0 at tail pads — how pad entries drop out of a
    scatter/histogram without any gather."""
    dt = wbuf.dtype if wbuf is not None else jnp.float64
    ones = jnp.ones(x.larray.shape, dtype=dt) if wbuf is None else wbuf.astype(dt)
    if x.pad_count == 0:
        return ones
    idx = jax.lax.broadcasted_iota(jnp.int32, x.larray.shape, x.split)
    return jnp.where(idx < x.shape[x.split], ones, jnp.zeros((), dtype=dt))


def _global_minmax(x: DNDarray):
    """(min, max) of a DNDarray's logical values — one device dispatch pair,
    ONE host sync. Pads are neutralized per-extreme (dtype max on the
    min side, dtype min on the max side), so any split/pad layout works."""
    from .manipulations import _sort_fill

    if x.pad_count:
        lo_buf = x._masked(_sort_fill(x, descending=False))
        hi_buf = x._masked(_sort_fill(x, descending=True))
    else:
        lo_buf = hi_buf = x.larray
    # XLA's reduce-min/max compare with `lhs < rhs`, which can silently drop
    # NaN depending on reduction order — carry an explicit NaN flag in the
    # same fused transfer (pads are finite fills, so they can't set it)
    nan_flag = jnp.isnan(lo_buf).any().astype(lo_buf.dtype)
    mn, mx, has_nan = np.asarray(
        jnp.stack([jnp.min(lo_buf), jnp.max(hi_buf), nan_flag])
    )
    if has_nan:
        return np.nan, np.nan
    return mn, mx


def _sanitize_range(lo: float, hi: float):
    """numpy's histogram range rules: finite, ordered, degenerate widened."""
    lo, hi = float(lo), float(hi)
    if not (np.isfinite(lo) and np.isfinite(hi)):
        raise ValueError(f"supplied range of [{lo}, {hi}] is not finite")
    if lo > hi:
        raise ValueError("max must be larger than min in range parameter")
    if lo == hi:
        return lo - 0.5, hi + 0.5
    return lo, hi


def bincount(x: DNDarray, weights: Optional[DNDarray] = None, minlength: int = 0) -> DNDarray:
    """Occurrence counts of non-negative ints (reference statistics.py:375:
    local bincount + Allreduce). Result is replicated.

    On a split array this is DISTRIBUTED: a `shard_map` kernel scatter-adds
    each shard's physical buffer into its local (nbins,) histogram (pads
    carry weight 0) and one psum over ICI combines them — only the global
    max crosses to the host (to size the output). The replicated jnp path
    handles the rest."""
    if x.ndim != 1:
        raise ValueError("object too deep for desired array")
    if x.split is not None and x.comm.size > 1 and x.size > 0:
        comm = x.comm
        mn, mx = (builtins.int(v) for v in _global_minmax(x))
        if mn < 0:
            raise ValueError("bincount: input must have no negative elements")
        nbins = builtins.max(mx + 1, builtins.int(minlength))
        wbuf = _aligned_weights_buf(x, weights)
        vw = _valid_weights(x, wbuf)
        acc = jnp.float64 if weights is not None else jnp.int64
        buf = x._masked(0)  # pads scatter into bin 0 with weight 0

        def kernel(vals, w):
            h = jnp.zeros((nbins,), dtype=acc).at[vals].add(w.astype(acc))
            # histogram counts are exact by contract — never compressed
            return comm.psum(h, precision="off")

        spec = comm.spec(0, 1)
        hist = jax.shard_map(
            kernel, mesh=comm.mesh, in_specs=(spec, spec),
            out_specs=comm.spec(None, 1),
        )(buf, vw)
        return DNDarray.from_logical(hist, None, x.device, x.comm)
    log = x._logical()
    if x.size > 0 and builtins.int(jnp.min(log)) < 0:
        # numpy raises; jnp.bincount silently drops negatives
        raise ValueError("bincount: input must have no negative elements")
    w = weights._logical() if isinstance(weights, DNDarray) else weights
    res = jnp.bincount(log, weights=w, minlength=minlength)
    return DNDarray.from_logical(res, None, x.device, x.comm)


def cov(m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None) -> DNDarray:
    """Covariance matrix estimate (reference statistics.py `cov`, built on
    distributed matmul). Variables × observations layout per rowvar."""
    if ddof is not None and not isinstance(ddof, builtins.int):
        raise ValueError("ddof must be integer")
    if m.ndim > 2:
        raise ValueError("m has more than 2 dimensions")
    x = m
    if x.ndim == 1:
        x = DNDarray.from_logical(x._logical()[None, :], None, x.device, x.comm)
    if not rowvar and x.shape[0] != 1:
        from .linalg import transpose

        x = transpose(x)
    if y is not None:
        yy = y
        if yy.ndim == 1:
            yy = DNDarray.from_logical(yy._logical()[None, :], None, y.device, y.comm)
        if not rowvar and yy.shape[0] != 1:
            from .linalg import transpose

            yy = transpose(yy)
        from . import manipulations

        x = manipulations.concatenate([x, yy], axis=0)
    if ddof is None:
        ddof = 0 if bias else 1
    n = x.shape[1]
    from . import arithmetics
    from .linalg import matmul, transpose

    mu = mean(x, axis=1)
    centered = arithmetics.sub(x, DNDarray.from_logical(mu._logical()[:, None], None, x.device, x.comm))
    fact = n - ddof
    c = matmul(centered, transpose(centered))
    return arithmetics.div(c, fact)


def _hist_distributed(x: DNDarray, edges: np.ndarray, weights):
    """Histogram counts of a split array as a DISTRIBUTED algorithm: each
    shard histograms its (raveled) physical buffer locally — tail pads carry
    weight 0, binning is order-independent so ANY split axis works — and one
    psum over ICI combines the per-shard counts (the reference's local hist
    + Allreduce, statistics.py:375/:509, as one shard_map kernel).
    ``edges`` are the precomputed float64 bin edges. Returns the replicated
    (nbins,) float64 counts."""
    comm = x.comm
    wbuf = _aligned_weights_buf(x, weights)
    vw = _valid_weights(x, wbuf)
    buf = x._masked(0)

    def kernel(vals, w):
        # bin in float64 against float64 edges on EVERY path (weighted,
        # unweighted, distributed, replicated): jnp.histogram's binning
        # dtype otherwise shifts with the weights argument, making the same
        # f32 data land differently per path. The f64 comparison is the
        # exact binning; numpy's f32 uniform-bin fast path computes indices
        # in f32 and may differ by O(1) counts on edge-straddling values
        # (numpy f32 disagrees with numpy f64 on the same data) — we match
        # numpy exactly for f64 input and match exact-comparison semantics
        # for everything else
        h, _ = jnp.histogram(
            vals.ravel().astype(jnp.float64), bins=edges, weights=w.ravel()
        )
        return comm.psum(h, precision="off")  # exact counts

    spec = comm.spec(x.split, x.ndim)
    return jax.shard_map(
        kernel, mesh=comm.mesh, in_specs=(spec, spec), out_specs=comm.spec(None, 1)
    )(buf, vw)


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins in [min, max]; values outside the
    range are ignored (reference statistics.py `histc`; local hist +
    Allreduce). Replicated result; distributed algorithm on split inputs
    (:func:`_hist_distributed`)."""
    lo, hi = float(min), float(max)
    if lo == 0.0 and hi == 0.0 and input.size > 0:
        lo, hi = _global_minmax(input)  # fused pass, one host sync
    lo, hi = _sanitize_range(lo, hi)
    edges = np.linspace(lo, hi, builtins.int(bins) + 1)
    if input.split is not None and input.comm.size > 1 and input.size > 0:
        hist = _hist_distributed(input, edges, None)
    else:
        hist, _ = jnp.histogram(
            input._logical().ravel().astype(jnp.float64), bins=edges
        )
    res = DNDarray.from_logical(hist.astype(input.dtype.jnp_type()), None, input.device, input.comm)
    if out is not None:
        out.larray = res.larray
        return out
    return res


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """numpy-style histogram (reference statistics.py `histogram`).
    Distributed algorithm on split inputs — per-shard counts + psum
    (:func:`_hist_distributed`); ``weights`` follows numpy semantics on
    every path."""
    if hasattr(bins, "__len__"):
        edges_np = np.asarray(bins, dtype=np.float64)
    else:
        if range is not None:
            lo, hi = float(range[0]), float(range[1])
        elif a.size:
            lo, hi = _global_minmax(a)  # fused pass, one host sync
        else:
            lo, hi = 0.0, 1.0
        lo, hi = _sanitize_range(lo, hi)
        edges_np = np.linspace(lo, hi, builtins.int(bins) + 1)
    if a.split is not None and a.comm.size > 1 and a.size > 0:
        hist = _hist_distributed(a, edges_np, weights)
        if weights is None:
            hist = hist.astype(jnp.int64)
    else:
        w = weights._logical().ravel() if isinstance(weights, DNDarray) else (
            jnp.asarray(weights).ravel() if weights is not None else None
        )
        hist, _ = jnp.histogram(
            a._logical().ravel().astype(jnp.float64), bins=edges_np, weights=w
        )
    if density:
        db = jnp.asarray(np.diff(edges_np))
        hist = hist / db / hist.sum()
    return (
        DNDarray.from_logical(hist, None, a.device, a.comm),
        DNDarray.from_logical(jnp.asarray(edges_np), None, a.device, a.comm),
    )


def _pallas_moments_fused(
    x: DNDarray, want: str, ddof: int = 0, interpret: bool = False
):
    """Graft ``x``'s pending fused elementwise chain into the pallas
    column-moments kernel (Fusion 2.0 pre-map): ONE cached program (site
    ``fusion_moments``) computing chain → pad-zero mask → single-read
    Welford moments — the chain never flushes into its own dispatch.
    Returns the replicated result buffer (mean for ``want='mean'``,
    ``M2/(n-ddof)`` for ``want='var'``) or None when nothing is pending /
    Fusion 2.0 is off."""
    from . import fusion, program_cache
    from .pallas_moments import column_moments, sharded_column_moments

    if not fusion.reduce_active():
        return None
    plan = fusion.pending_plan(x)
    if plan is None:
        return None
    sig, plan_t, args = plan
    comm = x.comm
    n = int(x.shape[0])
    sharded = comm.size > 1
    need_mask = bool(sharded and x.pad_count)
    key = sig + (
        ("moments", want, int(ddof), n, sharded, need_mask, interpret),
    )

    def build():
        chain = fusion.plan_program(plan_t)

        def prog(*bufs):
            val = chain(*bufs)
            if need_mask:
                # mask AFTER the chain: pad rows must enter the kernel
                # finite (0·inf inside the Welford combine would poison)
                val = fusion._mask_fill(val, dim=0, extent=n, fill=0.0)
            if sharded:
                mu, m2 = sharded_column_moments(
                    comm, val, n, interpret=interpret
                )
            else:
                mu, m2 = column_moments(val, n, interpret=interpret)
            if want == "mean":
                return mu
            return m2 / (n - ddof)

        return prog

    fn = program_cache.cached_program(
        "fusion_moments", key, build, comm=comm,
        out_shardings=comm.replicated() if sharded else None,
    )
    buf = fn(*args)
    fusion._note_absorbed(x, "moments_absorb", want=want)
    return buf


def chunk_moments(x: DNDarray, interpret: bool = False) -> Tuple:
    """Per-chunk column-moment carry ``(n, mean (d,), M2 (d,))`` over the
    rows of a 2-D chunk — the device half of
    :class:`heat_tpu.streaming.StreamingMoments` (ISSUE 16).

    ONE :func:`~heat_tpu.core.program_cache.cached_program` per
    (chunk shape, split) at site ``streaming.moments``: a steady stream
    of equal-shaped chunks re-enters the same warm executable every
    ``partial_fit`` (the zero-compile oracle pins
    ``site_stats("streaming.")``). On TPU the program drives the
    single-HBM-read pallas Welford kernel
    (:func:`~heat_tpu.core.pallas_moments.column_moments` /
    the sharded psum-merge variant); elsewhere a masked one-pass XLA
    form computes the identical carry. Chunk carries combine across
    ``partial_fit`` calls via :func:`pallas_moments.chan_merge` — the
    same merge rule the kernel applies across row blocks."""
    from . import program_cache
    from .pallas_moments import (
        column_moments,
        pallas_moments_applicable,
        sharded_column_moments,
    )

    if not isinstance(x, DNDarray):
        raise TypeError(f"chunk_moments needs a DNDarray, got {type(x)}")
    if x.ndim != 2:
        raise ValueError("chunk_moments needs a 2-D (rows, features) chunk")
    comm = x.comm
    n = builtins.int(x.shape[0])
    if n == 0:
        raise ValueError("chunk_moments: empty chunk (0 rows)")
    d = builtins.int(x.shape[1])
    xb = x._masked(0)  # tail pads zeroed (and weighted out below)
    sharded = comm.size > 1 and x.split is not None
    use_pallas = pallas_moments_applicable(
        comm.size, x.split, x.ndim, 0, d, xb.dtype
    )
    key = (
        "chunk_moments", tuple(xb.shape), str(xb.dtype), x.split, n,
        use_pallas, interpret,
    )

    def build():
        def prog(xv):
            if use_pallas:
                if comm.size > 1:
                    mu, m2 = sharded_column_moments(
                        comm, xv, n, interpret=interpret
                    )
                else:
                    mu, m2 = column_moments(xv, n, interpret=interpret)
                return mu, m2
            # XLA fallback: masked one-pass (sum, centered square sum).
            # Pad rows sit at GLOBAL tail indices (the physical-buffer
            # invariant every fitter relies on, cf. lasso._cd_fit)
            w = (jnp.arange(xv.shape[0]) < n).astype(xv.dtype)
            ns = jnp.sum(w)
            mu = (w @ xv) / ns
            dc = (xv - mu[None, :]) * w[:, None]
            m2 = jnp.sum(dc * dc, axis=0)
            return mu, m2

        return prog

    fn = program_cache.cached_program(
        "streaming.moments", key, build, comm=comm,
        out_shardings=comm.replicated() if sharded else None,
    )
    mu, m2 = fn(xb)
    return n, mu, m2


def _central_moment(x: DNDarray, axis, k: int):
    """E[(x-μ)^k] with pad-safe masking."""
    from . import arithmetics

    mu = mean(x, axis, keepdims_internal=True)
    d = arithmetics.sub(x, mu)
    p = arithmetics.pow(d, k)
    return mean(p, axis)


def kurtosis(x: DNDarray, axis=None, fisher: bool = True, bias: bool = True) -> DNDarray:
    """Kurtosis (Fisher by default; reference statistics.py `kurtosis`)."""
    from . import arithmetics

    m2 = _central_moment(x, axis, 2)
    m4 = _central_moment(x, axis, 4)
    res = arithmetics.div(m4, arithmetics.pow(m2, 2))
    if not bias:
        n = float(_reduced_count(x, sanitize_axis(x.shape, axis) if axis is not None else None))
        # standard unbiased correction
        g2 = res - 3.0
        res = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2 + 6.0) + 3.0
    if fisher:
        res = arithmetics.sub(res, 3.0)
    return res


def max(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Maximum along axis (reference statistics.py `max` via Allreduce MAX)."""
    return reduce_op(jnp.max, x, axis, neutral=_neutral_extreme(x, True), out=out, keepdims=keepdims)


def maximum(x1, x2, out=None) -> DNDarray:
    """Elementwise maximum (reference statistics.py `maximum`)."""
    return binary_op(jnp.maximum, x1, x2, out)


def mean(x: DNDarray, axis=None, keepdims_internal: bool = False, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean (reference statistics.py `mean`: single-pass (n, μ)
    Allreduce merge :803-828; here masked sum / logical count).

    The TPU f32 axis-0 2-D case routes through the SAME
    `column_moments` Pallas call as :func:`var` — deliberately identical
    operands, so a program computing both (the statistical-moments
    pattern) CSEs the two custom calls into ONE kernel execution: mean
    AND var from a single HBM read of X."""
    from . import arithmetics

    if (
        axis == 0
        and not keepdims
        and not keepdims_internal
        and isinstance(x, DNDarray)
        and x.ndim == 2  # gate BEFORE x.shape[1] — 1-D axis=0 is legal
        and x.split in (None, 0)
    ):
        from .pallas_moments import (
            column_moments,
            pallas_moments_applicable,
            sharded_column_moments,
        )

        if pallas_moments_applicable(
            x.comm.size, x.split, x.ndim, 0, x.shape[1],
            x.dtype.jnp_type(),  # metadata, so a pending chain stays pending
        ):
            mu = _pallas_moments_fused(x, "mean")
            if mu is None:
                if x.comm.size > 1:
                    mu, _m2 = sharded_column_moments(
                        x.comm, x._masked(0), x.shape[0]
                    )
                else:
                    mu, _m2 = column_moments(x.larray, x.shape[0])
            return DNDarray.from_logical(
                mu, None, x.device, x.comm,
                types.canonical_heat_type(mu.dtype),
            )

    keep = keepdims or keepdims_internal
    s = arithmetics.sum(x, axis, keepdims=keep)
    n = _reduced_count(x, sanitize_axis(x.shape, axis) if axis is not None else None)
    return arithmetics.div(s, n)


def median(x: DNDarray, axis=None, keepdims: bool = False) -> DNDarray:
    """Median (reference statistics.py `median` = percentile 50)."""
    return percentile(x, 50.0, axis=axis, keepdims=keepdims)


def min(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    return reduce_op(jnp.min, x, axis, neutral=_neutral_extreme(x, False), out=out, keepdims=keepdims)


def _is_inexact(x: DNDarray) -> bool:
    return jnp.issubdtype(x.dtype.jnp_type(), jnp.inexact)


def _with_out(res: DNDarray, out: Optional[DNDarray]) -> DNDarray:
    """numpy ``out=`` contract for the exact-int nan-variant routes, with
    the SAME shape/split/device validation the inexact routes get from
    ``reduce_op`` (a mismatched ``out`` must raise the sanitation error,
    not a low-level physical-shape one)."""
    if out is None:
        return res
    from . import sanitation

    sanitation.sanitize_out(out, tuple(res.shape), res.split, res.device)
    out.larray = res.larray.astype(out.dtype.jnp_type())
    return out


def nanmax(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Maximum ignoring NaN (reference statistics.py nan-family). Tail
    pads are filled with NaN inside the reduction — a value nanmax
    *ignores* — so pad rows can never win AND an all-NaN lane still
    yields NaN exactly as numpy does. Rides ``reduce_op``: a pending
    fused chain is absorbed into one map+reduce program (Fusion 2.0).
    Exact ints cannot hold NaN and route to :func:`max`."""
    if not _is_inexact(x):
        return max(x, axis, out=out, keepdims=keepdims)
    return reduce_op(jnp.nanmax, x, axis, neutral=float("nan"), out=out, keepdims=keepdims)


def nanmin(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Minimum ignoring NaN (see :func:`nanmax` for pad semantics)."""
    if not _is_inexact(x):
        return min(x, axis, out=out, keepdims=keepdims)
    return reduce_op(jnp.nanmin, x, axis, neutral=float("nan"), out=out, keepdims=keepdims)


def nanmean(x: DNDarray, axis=None, out=None, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean ignoring NaN. The NaN pad fill keeps tail pads out
    of BOTH the numerator and the divisor (a 0 fill would silently count
    them)."""
    if not _is_inexact(x):
        return _with_out(mean(x, axis, keepdims=keepdims), out)
    return reduce_op(jnp.nanmean, x, axis, neutral=float("nan"), out=out, keepdims=keepdims)


def nanvar(x: DNDarray, axis=None, ddof: int = 0, out=None, keepdims: bool = False) -> DNDarray:
    """Variance ignoring NaN (``ddof`` rides as a static kwarg, so the
    call still fuses with a pending chain)."""
    if not _is_inexact(x):
        return _with_out(var(x, axis, ddof=ddof, keepdims=keepdims), out)
    return reduce_op(
        jnp.nanvar, x, axis, neutral=float("nan"), out=out,
        keepdims=keepdims, ddof=builtins.int(ddof),
    )


def nanstd(x: DNDarray, axis=None, ddof: int = 0, out=None, keepdims: bool = False) -> DNDarray:
    """Standard deviation ignoring NaN."""
    if not _is_inexact(x):
        return _with_out(std(x, axis, ddof=ddof, keepdims=keepdims), out)
    return reduce_op(
        jnp.nanstd, x, axis, neutral=float("nan"), out=out,
        keepdims=keepdims, ddof=builtins.int(ddof),
    )


def minimum(x1, x2, out=None) -> DNDarray:
    return binary_op(jnp.minimum, x1, x2, out)


_PERCENTILE_METHODS = ("linear", "lower", "higher", "midpoint", "nearest")


def _percentile_sorted_axis(x: DNDarray, qa, interpolation: str, ax: builtins.int):
    """Distributed percentile along the SPLIT axis (any rank; ndim==1 is
    the ax=0 special case) — beats the reference's rank-0 gather
    (statistics.py:1406-1441): distributed sort along the axis (odd-even
    merge network over ICI, each lane independent), then a replicated
    sharded gather of ONLY the order-statistic slices the interpolation
    method reads. Returns a float64 jnp array shaped (len(q), *rest) with
    the reduced axis moved out, numpy-style."""
    from . import logical as lg
    from . import manipulations
    from .indexing import _sharded_take_fn

    n = x.shape[ax]
    q_flat = np.atleast_1d(np.asarray(qa, dtype=np.float64))
    vals, _ = manipulations.sort(x, axis=ax)
    # bracketing order statistics; indices are host-computable (q, n
    # static). np.round is exact half-to-even — numpy's 'nearest' rule
    pos = q_flat / 100.0 * (n - 1)
    m = len(q_flat)
    if interpolation == "lower":
        idx = np.floor(pos).astype(np.int64)
    elif interpolation == "higher":
        idx = np.ceil(pos).astype(np.int64)
    elif interpolation == "nearest":
        idx = np.round(pos).astype(np.int64)
    else:  # linear / midpoint need both brackets
        i0 = np.floor(pos).astype(np.int64)
        idx = np.concatenate([i0, np.ceil(pos).astype(np.int64)])
    take = _sharded_take_fn(x.comm, ax, None, x.ndim)
    pl = take(vals.larray, jnp.asarray(idx))
    pl = jnp.moveaxis(pl, ax, 0).astype(jnp.float64)  # (m or 2m, *rest)
    if interpolation == "linear":
        frac = jnp.asarray(pos - i0).reshape((m,) + (1,) * (x.ndim - 1))
        res = pl[:m] + (pl[m:] - pl[:m]) * frac
    elif interpolation == "midpoint":
        res = (pl[:m] + pl[m:]) / 2.0
    else:  # lower / higher / nearest gathered exactly their picks
        res = pl
    if jnp.issubdtype(x.dtype.jnp_type(), jnp.floating):
        # numpy: a NaN anywhere in a lane makes that lane's percentiles NaN
        # (the sort pushed NaNs to the lane tail, so picks alone can't tell)
        nan_lane = lg.any(lg.isnan(x), axis=ax).larray  # replicated (*rest)
        res = jnp.where(nan_lane[None] if x.ndim > 1 else nan_lane, jnp.nan, res)
    return res


def percentile(x: DNDarray, q, axis=None, out=None, interpolation: str = "linear", keepdims: bool = False) -> DNDarray:
    """q-th percentile. Reductions over the split axis (1-D global, or n-D
    along the split axis) are a DISTRIBUTED algorithm —
    :func:`_percentile_sorted_axis`: distributed sort + order-statistic
    slice gather; otherwise one jnp.percentile over the logical view
    (reference statistics.py:1406-1441 gathers per-rank partials). Result
    replicated either way."""
    qa = jnp.asarray(q, dtype=jnp.float64)
    qv = np.asarray(qa)
    if np.any(~((qv >= 0.0) & (qv <= 100.0))):
        # numpy raises on every path (incl. NaN q, which compares False to
        # both bounds); jnp.percentile does not — check here
        raise ValueError("percentiles must be in the range [0, 100]")
    q_shape = tuple(qa.shape)
    if qa.ndim > 1:
        # numpy accepts n-D q with the q dims leading the result; jnp only
        # takes rank<=1 — flatten here, restore the q shape at the end
        qa = qa.ravel()
    ax = sanitize_axis(x.shape, axis) if axis is not None else None
    if (
        x.split is not None
        and x.comm.size > 1
        and x.shape[x.split] > 0
        and qa.size > 0
        and interpolation in _PERCENTILE_METHODS
        and (
            (x.ndim == 1 and (ax is None or ax == 0 or ax == (0,)))
            or (x.ndim > 1 and (ax == x.split or ax == (x.split,)))
        )
    ):
        res = _percentile_sorted_axis(x, qa, interpolation, x.split)
        if not qa.ndim:
            res = res[0]  # scalar q: rest dims only
        if keepdims:
            off = 1 if qa.ndim else 0
            res = jnp.expand_dims(res, x.split + off)
        # falls through to the shared reshape/astype/wrap/out epilogue
    elif interpolation == "nearest":
        log = x._logical()
        # jnp.percentile's 'nearest' rounds half positions down; numpy
        # rounds half to even — select from the sorted values with
        # jnp.round (which IS half-to-even). Works for any axis form by
        # collapsing the reduced axes into one; NaN propagation restored
        # explicitly (jnp.sort pushes NaN to the end).
        axes = (
            tuple(range(log.ndim))
            if ax is None
            else ((ax,) if isinstance(ax, builtins.int) else tuple(ax))
        )
        rest = log.ndim - len(axes)
        moved = jnp.moveaxis(log, axes, tuple(range(rest, log.ndim)))
        arr2 = moved.reshape(moved.shape[:rest] + (-1,))
        n = arr2.shape[-1]
        srt = jnp.sort(arr2, axis=-1)
        # indices are host-computable (q and n are static) — np.round is
        # exact half-to-even, while jnp.round under the TPU backend's
        # emulated float64 mis-rounds exact half positions
        idx = jnp.asarray(
            np.round(np.asarray(qa) / 100.0 * (n - 1)).astype(np.int32)
        )
        res = jnp.take(srt, idx, axis=-1)
        if qa.ndim:
            res = jnp.moveaxis(res, -1, 0)  # the q dim leads, as in numpy
        nanmask = jnp.isnan(arr2).any(axis=-1)
        res = jnp.where(nanmask, jnp.nan, res)
        if keepdims:
            # re-insert length-1 dims at the original reduced positions
            # (shifted by one when a leading q dim is present)
            off = 1 if qa.ndim else 0
            # result currently carries the non-reduced dims in their
            # original relative order — map each kept dim back, inserting
            # the reduced ones
            for a in sorted(axes):
                res = jnp.expand_dims(res, a + off)
    else:
        res = jnp.percentile(x._logical(), qa, axis=axis, method=interpolation, keepdims=keepdims)
    if len(q_shape) > 1:
        res = res.reshape(q_shape + tuple(res.shape[1:]))
    res = res.astype(jnp.float64)
    out_arr = (
        DNDarray(res, tuple(res.shape), types.canonical_heat_type(res.dtype), None, x.device, x.comm, True)
        if res.ndim
        else DNDarray(res, (), types.canonical_heat_type(res.dtype), None, x.device, x.comm, True)
    )
    if out is not None:
        out.larray = out_arr.larray.astype(out.dtype.jnp_type())
        return out
    return out_arr


def skew(x: DNDarray, axis=None, unbiased: bool = True) -> DNDarray:
    """Skewness (reference statistics.py `skew`)."""
    from . import arithmetics, exponential

    m2 = _central_moment(x, axis, 2)
    m3 = _central_moment(x, axis, 3)
    res = arithmetics.div(m3, arithmetics.pow(m2, 1.5))
    if unbiased:
        n = float(_reduced_count(x, sanitize_axis(x.shape, axis) if axis is not None else None))
        if n > 2:
            res = arithmetics.mul(res, float(np.sqrt(n * (n - 1)) / (n - 2)))
    return res


def std(x: DNDarray, axis=None, ddof: int = 0, keepdims: bool = False) -> DNDarray:
    """Standard deviation (reference statistics.py `std`)."""
    from . import exponential

    return exponential.sqrt(var(x, axis, ddof=ddof, keepdims=keepdims))


def var(x: DNDarray, axis=None, ddof: int = 0, keepdims: bool = False) -> DNDarray:
    """Variance, two-pass (reference statistics.py `var`: Welford-style
    single-pass combine :1729-1758 — the two passes here fuse under XLA)."""
    from . import arithmetics

    if not isinstance(ddof, builtins.int):
        raise ValueError(f"ddof must be integer, is {type(ddof)}")
    if ddof not in (0, 1):
        raise ValueError("Heat currently supports ddof of 0 or 1 only")

    # single-device TPU f32 axis-0 on 2-D: one-HBM-read Welford kernel
    # (pallas_moments) instead of the two-read two-pass form
    if (
        axis == 0
        and not keepdims
        and isinstance(x, DNDarray)
        and x.ndim == 2  # gate BEFORE x.shape[1] — 1-D axis=0 is legal
        and x.split in (None, 0)
    ):
        from .pallas_moments import (
            column_moments,
            pallas_moments_applicable,
            sharded_column_moments,
        )

        if pallas_moments_applicable(
            x.comm.size, x.split, x.ndim, 0, x.shape[1],
            x.dtype.jnp_type(),  # metadata, so a pending chain stays pending
        ):
            out = _pallas_moments_fused(x, "var", ddof=ddof)
            if out is None:
                if x.comm.size > 1:
                    _mu, m2 = sharded_column_moments(
                        x.comm, x._masked(0), x.shape[0]
                    )
                else:
                    _mu, m2 = column_moments(x.larray, x.shape[0])
                out = m2 / (x.shape[0] - ddof)
            return DNDarray.from_logical(
                out, None, x.device, x.comm,
                types.canonical_heat_type(out.dtype),
            )

    mu = mean(x, axis, keepdims_internal=True)
    d = arithmetics.sub(x, mu)
    sq = arithmetics.mul(d, d)
    s = arithmetics.sum(sq, axis, keepdims=keepdims)
    n = _reduced_count(x, sanitize_axis(x.shape, axis) if axis is not None else None)
    return arithmetics.div(s, n - ddof)


DNDarray.argmax = lambda self, axis=None, out=None, keepdims=False: argmax(self, axis, out, keepdims)
DNDarray.argmin = lambda self, axis=None, out=None, keepdims=False: argmin(self, axis, out, keepdims)
DNDarray.max = lambda self, axis=None, out=None, keepdims=False: max(self, axis, out, keepdims)
DNDarray.min = lambda self, axis=None, out=None, keepdims=False: min(self, axis, out, keepdims)
DNDarray.mean = lambda self, axis=None, keepdims=False: mean(self, axis, keepdims=keepdims)
DNDarray.std = lambda self, axis=None, ddof=0, keepdims=False: std(self, axis, ddof, keepdims)
DNDarray.var = lambda self, axis=None, ddof=0, keepdims=False: var(self, axis, ddof, keepdims)
DNDarray.average = lambda self, axis=None, weights=None, returned=False: average(self, axis, weights, returned)
DNDarray.median = lambda self, axis=None, keepdims=False: median(self, axis, keepdims)
