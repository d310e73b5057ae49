"""What stands between a query or key projection and the flash kernels
(:class:`heat_tpu.nn.transformer.MultiHeadAttention`) as one Pallas TPU kernel
each way: the RMS norm over a head (or over the whole row), rotary positions
in the rotate-half form, the cast to the kernels' type and the kernels' layout.

Both kernels work in the flash kernels' own layout, ``(B, H, T, D)``, on
tiles of (a few heads, rows). ``qk_prep_fwd`` reads the projection's output
head-major, ``(B, H, T, W)``, once and writes ``(B, H, T, D)`` in the
attention's type, which ``flash_attention_head_major`` consumes as it stands.
:func:`qk_prep` hands it the projection's ``(B, T, H, W)`` transposed, and no
copy is made for that: XLA lays a projection's output out for its consumer (it
wrote the queries head-major for its own transposes before). With ``W = 2 D`` a
head's first ``D`` lanes are its query and the rest its gate (``attn_gate``): the
gates' lanes are never read. ``qk_prep_bwd`` keeps ``x`` and the gain alone,
forms the normalised rows again in VMEM, takes the cotangent through rotary's
transpose and the norm's derivative and writes the cotangent of the ``D`` lanes
it read and the gain's summed over a row block's positions (the row blocks are
summed outside).

Float32 throughout, whatever ``x`` is stored as: the mean of squares, the
gain, the angles' cosines and sines (XLA's, made from ``theta`` as
:func:`heat_tpu.nn.transformer.rotary` makes them), the cotangents; one
rounding to the attention's type at the end of the forward pass.

Both kernels are called through a module-level ``jax.jit`` inside
``pallas_delta.same_trace_context()``: every attention layer of a model, in each
of its passes, shares one trace of a body for each shape it is called at. Both
tell XLA what they cost (``pl.CostEstimate``): its scheduler takes a custom
call it knows nothing about for no time at all, and then finds no interval to
prefetch the flash kernels' keys and values into VMEM behind these calls.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_delta import same_trace_context

_F32 = jnp.float32
# heat_tpu enables jax_enable_x64: Python numbers in a kernel or an index map would
# trace as i64 and f64, which Mosaic refuses: 32-bit constants by name
_I0 = np.int32(0)
_ZERO = np.float32(0)
# A grid step's tile. On a v5e, one layer's queries of the Trinity-Mini cell (16,384 positions x 32 heads of 128 beside their
# gates), forward | backward by the host's clock with the tables' fusions inside: 0.72 | 1.11 ms at (512 rows, 4 heads),
# 0.69 | 1.10 at (512, 8), 0.70 | 1.10 at (1024, 4), 0.81 | 1.13 at (256, 4), 0.93 | 1.26 at (256, 2); Qwen3-Next's
# and OLMoE's shapes within 4% of their best at (512, 4) too (PR 50, chip call 1): 560-640 GB/s of the chip's 819 either way
ROWS = 512  # positions a grid step, at most
_LANES = 512  # lanes of whole heads a grid step, at most (a norm over the row takes every head)
_STEP_BYTES = 2 * 2**20  # of float32 input a grid step, at most: what shortens a step whose heads are the whole row
_LANE_TILE = 128


class Pass(NamedTuple):
    """What one pass does, read from the attention layer's fields: ``norm``
    ``None``, ``"head"`` (over each head's ``d`` lanes, the gain ``(1, d)``
    shared by the heads) or ``"row"`` (over all heads, the gain ``(H, d)``) at
    ``eps``; rotary at ``theta`` (``None``: none) on the leading ``fraction``
    of a head; the result's ``dtype``; ``rows`` positions a grid step at most."""

    d: int
    norm: Optional[str]
    eps: float
    theta: Optional[float]
    fraction: float
    dtype: Any
    rows: int
    interpret: bool


def takes_kernel(attn_impl: str, comm, d_head: int, norm: Optional[str], norm_kind: str, rotary: bool) -> bool:
    """Whether a layer's queries and keys take the kernels: on a TPU, under
    the flash kernels (whose layout the pass writes), on one device (under a
    sharded batch XLA's form stays), heads that fill whole lanes, and an RMS
    norm (``norm`` as :class:`Pass` has it, ``norm_kind`` the layer's ``norm``)
    or rotary to run: the four forms the training cells hold (a head norm with
    rotary and without, rotary alone, the norm over the row with rotary) each
    beat XLA's passes on the chip (PERF.md section 6, PR 50)."""
    if norm is not None and norm_kind not in ("rmsnorm", "rmsnorm_zero"):
        return False
    return (
        jax.default_backend() == "tpu" and attn_impl == "flash" and (comm is None or comm.size == 1)
        and d_head % _LANE_TILE == 0 and (norm is not None or rotary)
    )


def _tables(t: int, d: int, theta: float, fraction: float):
    """``(cos, signed sin, half)``: the angles' tables ``(t, span)`` over the
    leading lane tiles of a head that hold its rotated ``part = fraction d``
    lanes, as ``transformer.rotary`` forms them; past ``part`` the cosine is 1
    and the sine 0, and the sine of the first half of ``part`` carries the
    minus of ``(-x2, x1)``."""
    part = d if fraction >= 1.0 else int(d * fraction)
    span = min(d, -(-part // _LANE_TILE) * _LANE_TILE)
    inv = 1.0 / (theta ** (jnp.arange(0, part, 2, dtype=_F32) / part))
    ang = jnp.arange(t, dtype=_F32)[:, None] * inv[None, :]
    rest = span - part
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang), jnp.ones((t, rest), _F32)], axis=1)
    sin = jnp.concatenate([-jnp.sin(ang), jnp.sin(ang), jnp.zeros((t, rest), _F32)], axis=1)
    return cos, sin, part // 2


def _swap(z, half: int):
    """``z``'s two halves of ``half`` lanes each, swapped; zero past them."""
    span = z.shape[1]
    if 2 * half == span:
        return pltpu.roll(z, np.int32(half), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    low, high = pltpu.roll(z, np.int32(span - half), 1), pltpu.roll(z, np.int32(half), 1)
    return jnp.where(lane < np.int32(half), low, jnp.where(lane < np.int32(2 * half), high, _ZERO))


def _rotary(z, cos_ref, sin_ref, half, back=False):
    """Rotary on the leading lanes the tables cover, the rest as they are;
    ``back``: its transpose, the same with the sine inside the swap."""
    if cos_ref is None:
        return z
    span = cos_ref.shape[1]
    lead = z[:, :span]
    if back:
        lead = lead * cos_ref[...] + _swap(lead * sin_ref[...], half)
    else:
        lead = lead * cos_ref[...] + _swap(lead, half) * sin_ref[...]
    return lead if span == z.shape[1] else jnp.concatenate([lead, z[:, span:]], axis=1)


def _mean_square(xs, lanes: int):
    return functools.reduce(jnp.add, (jnp.sum(x * x, axis=1, keepdims=True) for x in xs)) * np.float32(1 / lanes)


def _after(refs, p: Pass):
    """``(gain, cos, sin, the rest)`` of the references behind a kernel's
    arrays: the gain where there is a norm, the tables where there is rotary."""
    refs = list(refs)
    gain_ref = refs.pop(0) if p.norm else None
    cos_ref, sin_ref = (refs.pop(0), refs.pop(0)) if p.theta is not None else (None, None)
    return gain_ref, cos_ref, sin_ref, refs


def _fwd_kernel(x_ref, *refs, p: Pass, half):
    heads = x_ref.shape[1]
    gain_ref, cos_ref, sin_ref, (out_ref,) = _after(refs, p)
    read = lambda j: x_ref[0, j].astype(_F32)  # noqa: E731
    eps = np.float32(p.eps)
    if p.norm == "row":
        r = jax.lax.rsqrt(_mean_square([read(j) for j in range(heads)], heads * p.d) + eps)
    for j in range(heads):
        n = read(j)
        if p.norm == "head":
            r = jax.lax.rsqrt(_mean_square([n], p.d) + eps)
        if p.norm:
            row = 0 if gain_ref.shape[0] == 1 else j  # one gain for every head, or a head's own
            n = n * r * gain_ref[row:row + 1, :]
        out_ref[0, j] = _rotary(n, cos_ref, sin_ref, half).astype(out_ref.dtype)


def _bwd_kernel(x_ref, g_ref, *refs, p: Pass, half, length):
    _, heads, rows, d = g_ref.shape
    gain_ref, cos_ref, sin_ref, (dx_ref, *dgain_ref) = _after(refs, p)
    back = lambda j: _rotary(g_ref[0, j].astype(_F32), cos_ref, sin_ref, half, back=True)  # noqa: E731
    if not p.norm:
        for j in range(heads):
            dx_ref[0, j] = back(j).astype(dx_ref.dtype)
        return
    (dgain_ref,) = dgain_ref
    read = lambda j: x_ref[0, j].astype(_F32)  # noqa: E731
    eps = np.float32(p.eps)
    shared = gain_ref.shape[0] == 1  # one gain for every head: its cotangent sums over them and over the grid's head steps
    # a row past the sequence's end is whatever its block held: selected out of the sum over rows, never multiplied
    at = pl.program_id(1) * np.int32(rows) + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    over_rows = lambda a: jnp.sum(a if length % rows == 0 else jnp.where(at < np.int32(length), a, _ZERO), axis=0, keepdims=True)  # noqa: E731

    @pl.when(pl.program_id(2) == _I0)
    def _():
        dgain_ref[...] = jnp.zeros(dgain_ref.shape, _F32)

    def through(j, x, r):
        """The head's normalised rows and the cotangent of them; the gain's of this head is summed in."""
        xhat = x * r
        dn = back(j)
        row = 0 if shared else j
        dgain_ref[0, 0, row:row + 1, :] += over_rows(dn * xhat)
        return xhat, dn * gain_ref[row:row + 1, :]

    if p.norm == "head":
        for j in range(heads):
            x = read(j)
            r = jax.lax.rsqrt(_mean_square([x], d) + eps)
            xhat, dxhat = through(j, x, r)
            m = jnp.sum(dxhat * xhat, axis=1, keepdims=True) * np.float32(1 / d)
            dx_ref[0, j] = (r * (dxhat - xhat * m)).astype(dx_ref.dtype)
        return
    # over the row: the mean of dxhat xhat runs over every head, so the heads are gone over twice, the cotangent of the
    # normalised rows waiting in the output's block meanwhile (float32 there: ``x`` is the projection's float32 output)
    r = jax.lax.rsqrt(_mean_square([read(j) for j in range(heads)], heads * d) + eps)
    m = jnp.zeros((rows, 1), _F32)
    for j in range(heads):
        xhat, dxhat = through(j, read(j), r)
        m = m + jnp.sum(dxhat * xhat, axis=1, keepdims=True)
        dx_ref[0, j] = dxhat.astype(dx_ref.dtype)
    m = m * np.float32(1 / (heads * d))
    for j in range(heads):
        dxhat = dx_ref[0, j].astype(_F32)
        dx_ref[0, j] = (r * (dxhat - read(j) * r * m)).astype(dx_ref.dtype)


def _tiling(t: int, heads: int, p: Pass):
    """``(rows a step, heads a step)``: whole heads of up to ``_LANES`` lanes
    that divide the heads (every head where the norm runs over the row), and
    rows of whole sublane tiles that keep a step's float32 input within
    ``_STEP_BYTES``."""
    if p.norm == "row":
        step = heads
    else:
        step = max(m for m in range(1, heads + 1) if heads % m == 0 and (m * p.d <= _LANES or m == 1))
    rows = min(p.rows, max(16, _STEP_BYTES // (4 * step * p.d) // 16 * 16))
    return (t if t <= rows else rows), step


# The innermost grid axis, the head steps, runs in turn: the tables' blocks stand still over it and the shared gain's
# cotangent sums over it in place. 16 MiB is what a kernel gets unasked (a backward step's tiles take ~10)
_COMPILER = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=16 * 2**20)


def _cost(elements: int, flops: int, bytes_moved: int):
    """What a kernel costs, for XLA's scheduler (which takes a custom call it
    knows nothing about for no time at all): a few operations and its reads
    and writes an element, one inverse root a row."""
    return pl.CostEstimate(flops=flops * elements, transcendentals=elements // _LANE_TILE, bytes_accessed=bytes_moved * elements)


def _plan(x, gain, p: Pass):
    """``(grid, tile, operands, their specs, half)`` of both kernels' calls on
    ``x (B, H, T, W)``: the grid ``(sequence, row step, head step)``, the
    block spec of a head-major tile ``(1, heads a step, rows, d)`` (of ``x``:
    a head's leading ``d`` lanes), and what a kernel reads behind its arrays:
    the gain whole, a row step's rows of the two tables."""
    b, heads, t, _ = x.shape
    rows, step = _tiling(t, heads, p)
    spec = lambda block, index: pl.BlockSpec(block, index, memory_space=pltpu.VMEM)  # noqa: E731
    tile = spec((1, step, rows, p.d), lambda b, i, h: (b, h, i, _I0))
    operands, specs, half = [], [], 0
    if p.norm:
        operands, specs = [gain], [spec(gain.shape, lambda b, i, h: (_I0, _I0))]
    if p.theta is not None:
        cos, sin, half = _tables(t, p.d, p.theta, p.fraction)
        operands += [cos, sin]
        specs += [spec((rows, cos.shape[1]), lambda b, i, h: (i, _I0))] * 2
    return (b, pl.cdiv(t, rows), heads // step), tile, operands, specs, half


@functools.partial(jax.jit, static_argnames=("p",))
def _forward(x, gain, *, p: Pass):
    grid, tile, operands, specs, half = _plan(x, gain, p)
    out = jax.ShapeDtypeStruct(x.shape[:3] + (p.d,), p.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, half=half),
        grid=grid,
        in_specs=[tile] + specs,
        out_specs=tile,
        out_shape=out,
        compiler_params=_COMPILER,
        cost_estimate=_cost(out.size, 10, x.dtype.itemsize + out.dtype.itemsize),
        interpret=p.interpret,
        name="qk_prep_fwd",
    )(x, *operands)


@functools.partial(jax.jit, static_argnames=("p",))
def _backward(x, gain, g, *, p: Pass):
    grid, tile, operands, specs, half = _plan(x, gain, p)
    out_specs, out_shape = [tile], [jax.ShapeDtypeStruct(g.shape, x.dtype)]
    if p.norm:  # the gain's cotangent a sequence and row step, summed below
        out_specs.append(pl.BlockSpec((1, 1) + gain.shape, lambda b, i, h: (b, i, _I0, _I0), memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct(grid[:2] + gain.shape, _F32))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, half=half, length=x.shape[2]),
        grid=grid,
        in_specs=[tile, tile] + specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_COMPILER,
        cost_estimate=_cost(g.size, 25, 2 * x.dtype.itemsize + g.dtype.itemsize),
        interpret=p.interpret,
        name="qk_prep_bwd",
    )(x, g, *operands)
    return out[0], (jnp.sum(out[1], axis=(0, 1)) if p.norm else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_major(x, gain, p: Pass):
    with same_trace_context():
        return _forward(x, gain, p=p)


def _head_major_fwd(x, gain, p):
    return _head_major(x, gain, p), (x, gain)


def _head_major_bwd(p, res, g):
    x, gain = res
    with same_trace_context():
        dx, dgain = _backward(x, gain, g, p=p)
    if x.shape[3] > p.d:  # nothing flows from here into the lanes that were not read
        dx = jnp.concatenate([dx, jnp.zeros(x.shape[:3] + (x.shape[3] - p.d,), dx.dtype)], axis=-1)
    return dx, dgain


_head_major.defvjp(_head_major_fwd, _head_major_bwd)


def qk_prep(x, gain, p: Pass):
    """``x (B, T, H, W)``, a projection's output, ``W`` ``p.d`` or (a head's
    query beside its gate) twice that, to ``(B, H, T, p.d)`` in ``p.dtype``:
    each head's leading ``p.d`` lanes, normalised (``gain``: ``(1, d)`` over a
    head, ``(H, d)`` over the row, ``None`` without a norm), rotated, rounded
    once, in the layout the flash kernels take."""
    return _head_major(x.transpose(0, 2, 1, 3), gain, p)
