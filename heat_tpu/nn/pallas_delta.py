"""One chunk step of the gated delta rule (:mod:`heat_tpu.nn.deltanet`): the
step's arithmetic for one head as a plain function of chunk-sized values, and
the two Pallas TPU kernels that run it for every head of a chunk with
everything of size chunk x chunk and chunk x head in VMEM.

:func:`chunk_step` takes a head's state ``S (Dk, Dv)`` and one chunk of its
``q, k (C, Dk)``, ``v (C, Dv)``, running log-decay and ``beta`` (rows ``(1,
C)``) and returns the state after the chunk and the chunk's output. Inside, the
chunk's writes depend on each other through a unit lower-triangular system
(:func:`unit_lower_inverse`). The XLA form of the rule maps it over the heads;
:func:`kernel_chunk_step` is the same function run by ``delta_chunk_fwd``, a
few value heads a grid step (a key head is read once for the value heads it
serves, through the index map), and differentiated by ``delta_chunk_bwd``,
which forms the chunk's quantities again in VMEM and takes the step's
``jax.vjp`` there: neither kernel writes an array of chunk x chunk.

Float32: the decays, ``beta``, the state, the solve (``HIGHEST`` products).
The chunk products take ``dtype`` operands and accumulate in float32, forward
and backward (a cotangent enters its two products as a ``dtype`` operand).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# heat_tpu enables jax_enable_x64: a Python 0 in an index map would trace as i64
_I0 = np.int32(0)
# and a Python float in a kernel as f64, which Mosaic refuses: float32 constants by name
_ZERO, _ONE = np.float32(0), np.float32(1)
# ``x @ y``, ``x @ y.T`` and ``x.T @ y`` as dimension numbers: no transposed copy
_DIMS = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}
# the cotangents of a product are products of the same three forms:
# form -> ((operands and form of dx), (of dy)), with g the output's cotangent
_TRANSPOSE = {
    "nn": (("g", "y", "nt"), ("x", "g", "tn")),
    "nt": (("g", "y", "nn"), ("g", "x", "tn")),
    "tn": (("y", "g", "nt"), ("x", "g", "nn")),
}


def _dot(x, y, form, precision=None):
    """``precision`` is for float32 operands (``None``: the caller's default);
    narrower operands are what they are, and Mosaic refuses them a precision."""
    if x.dtype != _F32:
        precision = jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(x, y, (_DIMS[form], ((), ())), precision=precision, preferred_element_type=_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(x, y, form, dtype):
    """A chunk product: ``dtype`` operands, float32 accumulation and result."""
    return _mm_fwd(x, y, form, dtype)[0]


def _mm_fwd(x, y, form, dtype):
    x, y = x.astype(dtype), y.astype(dtype)
    return _dot(x, y, form), (x, y)


def _mm_bwd(form, dtype, res, g):
    named = {"x": res[0], "y": res[1], "g": g.astype(dtype)}
    return tuple(_dot(named[a], named[b], f) for a, b, f in _TRANSPOSE[form])


_mm.defvjp(_mm_fwd, _mm_bwd)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a (C, C)``, float32:
    ``a`` is nilpotent, so the inverse is the finite sum of ``(-a)^j``, taken
    as the product of ``I + (-a)^(2^i)``. A doubling is one product: the power
    times ``[power | inverse so far]``, side by side, gives the next power and
    what the inverse gains (they are polynomials in ``a`` and commute), so the
    MXU's columns are filled twice over and no loop runs over rows. Its
    transpose needs the inverse alone (``-x^T g x^T``): no power is kept."""
    c = a.shape[-1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    right = cols >= c
    both = jnp.where(right, jnp.where(cols - c == rows, _ONE, _ZERO), jnp.concatenate([-a, a], axis=1))  # [-a | I]
    for _ in range(max(math.ceil(math.log2(c)), 1)):
        both = _dot(both[:, :c], both, "nn", _HIGHEST) + jnp.where(right, both, _ZERO)
    return both[:, c:]


def _unit_lower_inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, g):
    return (-_dot(_dot(inv, g, "tn", _HIGHEST), inv, "nt", _HIGHEST),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunk_step(state, q, k, v, run, beta, *, dtype):
    """One head over one chunk: ``state (Dk, Dv)``, ``q, k (C, Dk)``, ``v (C,
    Dv)``, ``run`` (the log of the decay since the chunk began) and ``beta``
    as rows ``(1, C)``, all float32. Returns the state after the chunk and the
    chunk's output ``(C, Dv)``. Values of two dimensions throughout, so that a
    Mosaic kernel can run it as it stands."""
    c = q.shape[0]
    mm = functools.partial(_mm, dtype=dtype)
    rows, cols = (jax.lax.broadcasted_iota(jnp.int32, (c, c), d) for d in (0, 1))
    # a row (1, C) as a column (C, 1), and its last entry down a column: masked sums
    # over the lanes, exact (no value of one element: Mosaic lays out two dimensions)
    column = lambda row: jnp.sum(jnp.where(rows == cols, row, _ZERO), axis=1, keepdims=True)  # noqa: E731
    last = lambda n: jnp.sum(  # noqa: E731
        jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, c), 1) == c - 1, run, _ZERO), axis=1, keepdims=True
    )
    run_c, beta_c = column(run), column(beta)
    at_or_below = rows >= cols
    decay = jnp.where(at_or_below, jnp.exp(jnp.where(at_or_below, run_c - run, _ZERO)), _ZERO)  # (C, C)
    grown = jnp.exp(run_c)

    k_beta = k * beta_c
    inside = jnp.where(rows > cols, mm(k_beta, k, "nt") * decay, _ZERO)
    solve = unit_lower_inverse(inside)
    writes = mm(solve, v * beta_c, "nn")  # at a zero state
    reads = mm(solve, k_beta * grown, "nn")  # what the state takes off them
    new = writes - mm(reads, state, "nn")
    to_end = k * jnp.exp(last(c) - run_c)
    after = state * jnp.exp(last(state.shape[0])) + mm(to_end, new, "tn")
    scores = mm(q, k, "nt") * decay
    return after, mm(q * grown, state, "nn") + mm(scores, new, "nn")


# -- the kernels -------------------------------------------------------------------------


def _heads_a_step(h, group):
    """Value heads a grid step: eight (a block of the decays' rows is eight
    sublanes; sixteen heads' backward pass no longer fits VMEM), whole key
    heads; or all of them where they are no more. ``None``: no such step."""
    if h % 8 == 0 and 8 % group == 0:
        return 8
    return h if h <= 8 else None


def _stacked(q_ref, k_ref, v_ref, run_ref, beta_ref, *, heads, group, dk, dv):
    """A grid step's heads along a leading axis: a key head once for each value
    head it serves, the decays' and beta's rows as ``(heads, 1, C)``."""
    of_key = lambda ref: jnp.stack([ref[0, :, j // group * dk:(j // group + 1) * dk] for j in range(heads)])  # noqa: E731
    rows = lambda ref: jnp.stack([ref[0, j:j + 1, :] for j in range(heads)])  # noqa: E731
    v = jnp.stack([v_ref[0, :, j * dv:(j + 1) * dv] for j in range(heads)])
    return of_key(q_ref), of_key(k_ref), v, rows(run_ref), rows(beta_ref)


def _fwd_kernel(s_ref, q_ref, k_ref, v_ref, run_ref, beta_ref, after_ref, o_ref, *, heads, group, dk, dv, dtype):
    # mapped over the heads, not looped: every product is then the heads' products
    # side by side, and one head's chain of dependent products hides behind the others'
    after, o = jax.vmap(functools.partial(chunk_step, dtype=dtype))(
        s_ref[0], *_stacked(q_ref, k_ref, v_ref, run_ref, beta_ref, heads=heads, group=group, dk=dk, dv=dv)
    )
    after_ref[0] = after
    for j in range(heads):
        o_ref[0, :, j * dv:(j + 1) * dv] = o[j]


def _bwd_kernel(
    s_ref, q_ref, k_ref, v_ref, run_ref, beta_ref, g_after_ref, g_o_ref,
    g_s_ref, g_q_ref, g_k_ref, g_v_ref, g_run_ref, g_beta_ref, *, heads, group, dk, dv, dtype,
):
    _, vjp = jax.vjp(
        jax.vmap(functools.partial(chunk_step, dtype=dtype)),
        s_ref[0], *_stacked(q_ref, k_ref, v_ref, run_ref, beta_ref, heads=heads, group=group, dk=dk, dv=dv)
    )
    g_o = jnp.stack([g_o_ref[0, :, j * dv:(j + 1) * dv] for j in range(heads)])
    g_s, g_q, g_k, g_v, g_run, g_beta = vjp((g_after_ref[0], g_o))
    g_s_ref[0] = g_s
    for j in range(heads):
        g_v_ref[0, :, j * dv:(j + 1) * dv] = g_v[j]
        g_run_ref[0, j:j + 1, :] = g_run[j]
        g_beta_ref[0, j:j + 1, :] = g_beta[j]
    for i in range(heads // group):  # a key head's cotangents: the sum over the value heads it serves
        g_q_ref[0, :, i * dk:(i + 1) * dk] = sum(g_q[j] for j in range(i * group, (i + 1) * group))
        g_k_ref[0, :, i * dk:(i + 1) * dk] = sum(g_k[j] for j in range(i * group, (i + 1) * group))


_STEP_INPUTS = ("state", "key", "key", "value", "gate", "gate")  # state, q, k, v, decays, beta


def _run(kernel, name, operands, results, dtype, interpret):
    """``kernel`` on a grid of (sequence, group of value heads). ``operands``
    are a chunk step's inputs (and, backward, the cotangents of its results):
    state ``(B, H, Dk, Dv)``, ``q, k (B, C, Hk Dk)``, ``v (B, C, H Dv)``, decays
    and ``beta`` ``(B, H, C)``; ``results`` name what comes back, by role."""
    state, q, _, v, run = operands[:5]
    b, h, dk, dv = state.shape
    c, group = run.shape[-1], h * dk // q.shape[-1]
    heads = _heads_a_step(h, group)
    spec = lambda block, index: pl.BlockSpec(block, index, memory_space=pltpu.VMEM)  # noqa: E731
    specs = {
        "state": spec((1, heads, dk, dv), lambda bi, gi: (bi, gi, _I0, _I0)),
        "key": spec((1, c, heads // group * dk), lambda bi, gi: (bi, _I0, gi)),
        "value": spec((1, c, heads * dv), lambda bi, gi: (bi, _I0, gi)),
        "gate": spec((1, heads, c), lambda bi, gi: (bi, gi, _I0)),
    }
    like = {"state": state, "key": q, "value": v, "gate": run}
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, group=group, dk=dk, dv=dv, dtype=dtype),
        grid=(b, h // heads),
        in_specs=[specs[role] for role in (_STEP_INPUTS + ("state", "value"))[:len(operands)]],
        out_specs=[specs[role] for role in results],
        out_shape=[jax.ShapeDtypeStruct(like[role].shape, _F32) for role in results],
        compiler_params=pltpu.CompilerParams(
            # eight heads' values of a backward step are past the 16 MiB a kernel gets unasked
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 2**20,
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def same_trace_context():
    """A context in which every call of a jitted function finds the one trace
    of it. JAX keys that trace on the abstract mesh in context, and an equation
    evaluated again under a transformation (a checkpoint's recomputation, a
    custom VJP's forward rule) brings the empty mesh where the program's first
    pass saw none: two keys, two traces of the same function. With the mesh in
    context named outright, none reads as the empty one from the first."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


# Each kernel is called through a module-level ``jax.jit``: the mixers of a model, in each
# of their passes, then share one trace of the kernel's body and one lowering to Mosaic,
# where a bare ``pallas_call`` is traced and lowered anew at every call site (a train step
# of three mixers, each run forward three times, holds twelve)
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _step_forward(state, q, k, v, run, beta, *, dtype, interpret):
    return tuple(_run(_fwd_kernel, "delta_chunk_fwd", (state, q, k, v, run, beta), ("state", "value"), dtype, interpret))


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _step_backward(*inputs_and_cotangents, dtype, interpret):
    return tuple(_run(_bwd_kernel, "delta_chunk_bwd", inputs_and_cotangents, _STEP_INPUTS, dtype, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def kernel_chunk_step(state, q, k, v, run, beta, dtype, interpret):
    """:func:`chunk_step` for every sequence and head of one chunk, as one
    Mosaic kernel: ``state (B, H, Dk, Dv)``, ``q, k (B, C, Hk Dk)`` (key head
    ``i`` serves value heads ``i r .. i r + r - 1``), ``v (B, C, H Dv)``,
    ``run`` and ``beta`` ``(B, H, C)``, float32. Returns the states after the
    chunk and the outputs ``(B, C, H Dv)``."""
    with same_trace_context():
        return _step_forward(state, q, k, v, run, beta, dtype=dtype, interpret=interpret)


def _kernel_chunk_step_fwd(state, q, k, v, run, beta, dtype, interpret):
    return kernel_chunk_step(state, q, k, v, run, beta, dtype, interpret), (state, q, k, v, run, beta)


def _kernel_chunk_step_bwd(dtype, interpret, inputs, cotangents):
    with same_trace_context():
        return _step_backward(*inputs, *cotangents, dtype=dtype, interpret=interpret)


kernel_chunk_step.defvjp(_kernel_chunk_step_fwd, _kernel_chunk_step_bwd)


def takes_kernel(q_shape, v_shape, chunk: int) -> bool:
    """Whether the rule's chunk step runs as the kernel for ``q (B, T, Hk,
    Dk)`` and ``v (B, T, H, Dv)``: on a TPU, head sizes that fill whole lanes, a
    chunk of whole sublane tiles (of ``dtype`` operands too), and heads that
    divide into grid steps."""
    (hk, dk), (h, dv) = q_shape[2:], v_shape[2:]
    return (
        jax.default_backend() == "tpu" and dk % 128 == 0 and dv % 128 == 0 and chunk % 16 == 0
        and h % hk == 0 and _heads_a_step(h, h // hk) is not None
    )
