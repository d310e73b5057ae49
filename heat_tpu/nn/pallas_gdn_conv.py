"""The Gated DeltaNet mixer's pass before the rule (:mod:`heat_tpu.nn.deltanet`)
as one Pallas TPU kernel each way: the causal depthwise convolution over the
projection's ``q | k | v`` channels, SiLU, the l2 norm of ``q`` and ``k`` over
their head (``q`` scaled by ``Dk^-1/2``), ``v`` passed through.

``gdn_conv_fwd`` reads the projection ``x (B, T, C)`` once and writes ``q, k
(B, T, Hk Dk)`` and ``v (B, T, Hv Dv)`` in float32, which the rule's split into
chunks takes without a copy. ``gdn_conv_bwd`` keeps ``x`` and the taps alone,
forms the pre-activation and the norms' factors again in VMEM, and writes the
cotangent of ``x`` and, summed over the positions, of the taps. A grid step
holds a tile of (rows, a few whole heads): a head's channels are the lane
axis, so its norm is a lane reduction and a tap a shift along the sublanes,
and a tile needs of its neighbours only the rows just before it (backward:
and just after it), read as a block of one sublane tile. The channel blocks
go through ``q``'s, ``k``'s and ``v``'s in turn; a grid step writes the one
it is in, and the index maps of the two others stand still meanwhile.

Both kernels are called through a module-level ``jax.jit``: every mixer of a
model, in each of its passes, shares one trace of the kernel's body and one
lowering to Mosaic, where a bare ``pallas_call`` is traced and lowered anew
at every call site.

Float32 throughout, whatever ``x`` is stored as: the convolution's sum, SiLU,
the norms, ``q``, ``k``, ``v`` and the taps' cotangent; the cotangent of ``x``
leaves in ``x``'s type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_delta import same_trace_context

_F32 = jnp.float32
# heat_tpu enables jax_enable_x64: Python numbers in a kernel or an index map would
# trace as i64 and f64, which Mosaic refuses: 32-bit constants by name
_I0, _I1 = np.int32(0), np.int32(1)
_ZERO, _ONE = np.float32(0), np.float32(1)
EPS = np.float32(1e-6)  # the l2 norm's, as ``deltanet.l2_normalise`` has it
# A grid step's tile. On a v5e, one sequence of the Qwen3-Next cell (8,192 positions x 8,192 float32 channels),
# forward | forward and backward: 1.09 | 2.33 ms at (256, 512), 1.05 | 2.50 at (256, 1024), 1.01 | 2.39 at (512, 512),
# 0.96 | 2.44 at (1024, 512), where XLA's passes take 4.27 | 10.73 alone (PR 45, chip call 1): either way about
# what the chip's memory gives (512 MiB forward at ~500 GB/s), so the smallest tile, which is the least code
ROWS = 256  # positions a grid step
_LANES = 512  # channels a grid step, at most
_HALO = 8  # rows of a neighbouring tile at hand: one float32 sublane tile


def _sublanes(dtype) -> int:
    """Rows of one sublane tile of ``dtype``; 0 where this kernel takes no such type."""
    return {4: 8, 2: 16}.get(jnp.dtype(dtype).itemsize, 0)


def _lanes(key_dim: int, value_dim: int, dk: int) -> int:
    """Channels a grid step: whole key heads, a divisor of ``q``'s, ``k``'s and
    ``v``'s channels, ``_LANES`` at most where a head is no wider."""
    heads = [m for m in range(1, key_dim // dk + 1) if key_dim % (m * dk) == 0 and value_dim % (m * dk) == 0]
    return dk * max([m for m in heads if m * dk <= _LANES] or heads[:1])


def takes_kernel(x_shape, x_dtype, w_shape, key_heads: int, dk: int, value_heads: int, dv: int) -> bool:
    """Whether the pass runs as the kernels for ``x (B, T, C)`` and taps ``w
    (C, K)``: on a TPU, head sizes that fill whole lanes, value channels that
    divide into key heads' widths, a length of whole sublane tiles of ``x``'s
    type, and taps that reach no further back than one tile."""
    key_dim, value_dim = key_heads * dk, value_heads * dv
    tile = _sublanes(x_dtype)
    return (
        jax.default_backend() == "tpu" and dk % 128 == 0 and dv % 128 == 0 and value_dim % dk == 0
        and tuple(w_shape[:1]) == (2 * key_dim + value_dim,) == tuple(x_shape[2:])
        and tile > 0 and x_shape[1] % tile == 0 and 1 <= w_shape[1] <= _HALO + 1
    )


def _conv(ext, w, n: int):
    """``n`` rows of the causal convolution from ``ext``, which holds ``_HALO``
    rows before the first of them: ``w`` is ``(taps, channels)``."""
    taps = w.shape[0]
    first = _HALO - (taps - 1)
    return functools.reduce(jnp.add, (ext[first + j:first + j + n] * w[j:j + 1] for j in range(taps)))


def _by_head(dk: int, fn, *arrays):
    """``fn`` over the heads of ``arrays (rows, heads x dk)``, side by side again."""
    heads = arrays[0].shape[1] // dk
    return [fn(*(a[:, h * dk:(h + 1) * dk] for a in arrays)) for h in range(heads)]


def _role(c, n_key: int):
    """0, 1, 2 for a channel block of ``q``, ``k``, ``v``."""
    return (c >= np.int32(n_key)).astype(jnp.int32) + (c >= np.int32(2 * n_key)).astype(jnp.int32)


def _fwd_kernel(prev_ref, x_ref, w_ref, q_ref, k_ref, v_ref, *, n_key, dk):
    i, c = pl.program_id(1), pl.program_id(2)
    x = x_ref[0].astype(_F32)
    prev = jnp.where(i == _I0, _ZERO, prev_ref[0].astype(_F32)[-_HALO:])  # nothing before the first position
    pre = _conv(jnp.concatenate([prev, x], axis=0), w_ref[...], x.shape[0])
    y = pre * jax.nn.sigmoid(pre)

    def normed(out_ref, scale):
        def one(yh):
            return yh * jax.lax.rsqrt(jnp.sum(yh * yh, axis=1, keepdims=True) + EPS) * scale
        for h, out in enumerate(_by_head(dk, one, y)):
            out_ref[0, :, h * dk:(h + 1) * dk] = out

    role = _role(c, n_key)
    pl.when(role == _I0)(lambda: normed(q_ref, np.float32(dk ** -0.5)))
    pl.when(role == _I1)(lambda: normed(k_ref, _ONE))

    @pl.when(role == np.int32(2))
    def _():
        v_ref[0] = y


def _bwd_kernel(
    prev_ref, x_ref, next_ref, w_ref, gq_ref, gq_next_ref, gk_ref, gk_next_ref, gv_ref, gv_next_ref,
    gx_ref, gw_ref, *, n_key, dk, length,
):
    c, i = pl.program_id(1), pl.program_id(2)
    rows, taps = x_ref.shape[1], w_ref.shape[0]
    w = w_ref[...]
    # the tile with a sublane tile of rows either side; what lies outside the sequence counts as zero
    ext = jnp.concatenate(
        [prev_ref[0].astype(_F32)[-_HALO:], x_ref[0].astype(_F32), next_ref[0].astype(_F32)[:_HALO]], axis=0
    )
    at = i * np.int32(rows) - np.int32(_HALO) + jax.lax.broadcasted_iota(jnp.int32, (rows + 2 * _HALO, 1), 0)
    ext = jnp.where((at >= _I0) & (at < np.int32(length)), ext, _ZERO)
    # the pre-activation again, for the tile and the rows after it whose convolution reads the tile
    pre = _conv(ext, w, rows + _HALO)
    gate = jax.nn.sigmoid(pre)
    y = pre * gate

    def through_norm(g_ref, g_next_ref, scale):
        def one(yh, gh):
            r = jax.lax.rsqrt(jnp.sum(yh * yh, axis=1, keepdims=True) + EPS)
            return (gh - yh * (r * r * jnp.sum(gh * yh, axis=1, keepdims=True))) * (r * scale)
        g = jnp.concatenate([g_ref[0], g_next_ref[0]], axis=0)
        return jnp.concatenate(_by_head(dk, one, y, g), axis=1)

    g_y = jax.lax.switch(_role(c, n_key), [
        lambda: through_norm(gq_ref, gq_next_ref, np.float32(dk ** -0.5)),
        lambda: through_norm(gk_ref, gk_next_ref, _ONE),
        lambda: jnp.concatenate([gv_ref[0], gv_next_ref[0]], axis=0),
    ])
    # a cotangent past the sequence's end is whatever its block held: selected out, never multiplied
    g_pre = jnp.where(at[_HALO:] < np.int32(length), g_y * gate * (_ONE + pre * (_ONE - gate)), _ZERO)
    g_x = functools.reduce(jnp.add, (g_pre[taps - 1 - j:taps - 1 - j + rows] * w[j:j + 1] for j in range(taps)))
    gx_ref[0] = g_x.astype(gx_ref.dtype)

    @pl.when(i == _I0)
    def _():
        gw_ref[...] = jnp.zeros(gw_ref.shape, _F32)

    first = _HALO - (taps - 1)
    for j in range(taps):
        gw_ref[0, j:j + 1, :] += jnp.sum(g_pre[:rows] * ext[first + j:first + j + rows], axis=0, keepdims=True)


def _tiling(x, key_heads, dk, rows):
    """``(rows a step, row steps, lanes a step, q's (= k's) channel steps, all channel steps)``."""
    _, t, channels = x.shape
    key_dim = key_heads * dk
    lanes = _lanes(key_dim, channels - 2 * key_dim, dk)
    rows = min(rows, t)
    return rows, pl.cdiv(t, rows), lanes, key_dim // lanes, channels // lanes


def _specs(x, rows, lanes, n_key, order):
    """Block specs of one grid: ``order`` turns the grid's indices into
    ``(sequence, row step, channel step)``. ``tile(n)`` is a block of ``n``
    rows at the row step, ``before(n)`` / ``after(n)`` the ``n`` rows (one
    sublane tile) before and after it, held inside the sequence; each takes
    the channel step as it is or, ``of=`` 0, 1, 2, as ``q``'s, ``k``'s or
    ``v``'s own, standing still while the grid is in the two others."""
    t = x.shape[1]
    spec = lambda block, index: pl.BlockSpec(block, lambda *g: index(*order(*g)), memory_space=pltpu.VMEM)  # noqa: E731

    def channel(c, of):
        if of is None:
            return c
        own = c - np.int32(of * n_key)
        return jnp.maximum(own, _I0) if of == 2 else jnp.minimum(jnp.maximum(own, _I0), np.int32(n_key - 1))

    def tile(n, of=None):
        return spec((1, n, lanes), lambda b, i, c: (b, i, channel(c, of)))

    def before(n, of=None):
        return spec((1, n, lanes), lambda b, i, c: (b, jnp.maximum(i * np.int32(rows // n) - _I1, _I0), channel(c, of)))

    def after(n, of=None):
        last = np.int32(t // n - 1)
        return spec((1, n, lanes), lambda b, i, c: (b, jnp.minimum((i + _I1) * np.int32(rows // n), last), channel(c, of)))

    return spec, tile, before, after


# The innermost grid axis runs in turn. Forward it is the channel steps: an output's block waits, unwritten or written,
# while the grid is in another's; backward the row steps: the taps' cotangent sums over them in place. And a step's
# float32 values are past the 16 MiB a kernel gets unasked
_COMPILER = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 2**20)


@functools.partial(jax.jit, static_argnames=("key_heads", "dk", "rows", "interpret"))
def _forward(x, w, *, key_heads, dk, rows, interpret):
    b, t, channels = x.shape
    rows, row_steps, lanes, n_key, steps = _tiling(x, key_heads, dk, rows)
    spec, tile, before, _ = _specs(x, rows, lanes, n_key, lambda bi, i, c: (bi, i, c))
    key = jax.ShapeDtypeStruct((b, t, key_heads * dk), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_key=n_key, dk=dk),
        grid=(b, row_steps, steps),
        in_specs=[before(_sublanes(x.dtype)), tile(rows), spec((w.shape[1], lanes), lambda bi, i, c: (_I0, c))],
        out_specs=[tile(rows, of=0), tile(rows, of=1), tile(rows, of=2)],
        out_shape=[key, key, jax.ShapeDtypeStruct((b, t, channels - 2 * key_heads * dk), _F32)],
        compiler_params=_COMPILER,
        interpret=interpret,
        name="gdn_conv_fwd",
    )(x, x, w.T)


@functools.partial(jax.jit, static_argnames=("key_heads", "dk", "rows", "interpret"))
def _backward(x, w, g_q, g_k, g_v, *, key_heads, dk, rows, interpret):
    b, t, channels = x.shape
    taps = w.shape[1]
    rows, row_steps, lanes, n_key, steps = _tiling(x, key_heads, dk, rows)
    spec, tile, before, after = _specs(x, rows, lanes, n_key, lambda bi, c, i: (bi, i, c))
    near = _sublanes(x.dtype)
    g_x, g_w = pl.pallas_call(
        functools.partial(_bwd_kernel, n_key=n_key, dk=dk, length=t),
        grid=(b, steps, row_steps),
        in_specs=[before(near), tile(rows), after(near), spec((taps, lanes), lambda bi, i, c: (_I0, c))]
        + [s for of in range(3) for s in (tile(rows, of), after(_HALO, of))],
        out_specs=[tile(rows), spec((1, taps, lanes), lambda bi, i, c: (bi, _I0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((b, taps, channels), _F32)],
        compiler_params=_COMPILER,
        interpret=interpret,
        name="gdn_conv_bwd",
    )(x, x, x, w.T, g_q, g_q, g_k, g_k, g_v, g_v)
    return g_x, jnp.sum(g_w, axis=0).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def conv_silu_norm(x, w, key_heads, dk, rows, interpret):
    """``x (B, T, C)``, the projection's ``q | k | v`` channels (``key_heads``
    heads of ``dk`` for ``q`` and for ``k``, the rest ``v``'s), and taps ``w
    (C, K)`` to ``q, k (B, T, key_heads dk)`` and ``v``, float32: ``silu`` of
    the causal depthwise convolution, ``q`` and ``k`` l2-normalised over
    their head, ``q`` times ``dk^-1/2``. ``rows`` positions a grid step."""
    with same_trace_context():
        return tuple(_forward(x, w, key_heads=key_heads, dk=dk, rows=rows, interpret=interpret))


def _conv_silu_norm_fwd(x, w, key_heads, dk, rows, interpret):
    return conv_silu_norm(x, w, key_heads, dk, rows, interpret), (x, w)


def _conv_silu_norm_bwd(key_heads, dk, rows, interpret, res, cotangents):
    with same_trace_context():
        return _backward(*res, *cotangents, key_heads=key_heads, dk=dk, rows=rows, interpret=interpret)


conv_silu_norm.defvjp(_conv_silu_norm_fwd, _conv_silu_norm_bwd)
