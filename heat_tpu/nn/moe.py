"""Mixture-of-experts layer — expert parallelism (ep) over the mesh.

The reference has no MoE (its parallelism is DP-only, SURVEY §2.5); this
is the TPU-native strategy expressed the XLA way: routing builds static
``(tokens, experts, capacity)`` dispatch/combine tensors (Switch top-1,
capacity-factor bounded — over-capacity tokens drop to the residual,
standard behavior), the dispatch/expert/combine contractions are three
einsums, and a single ``with_sharding_constraint`` on the expert axis
makes XLA insert the token all_to_alls — no hand-written collective
choreography, exactly the "let the compiler place the collectives"
design stance of the framework (SURVEY §7).

:class:`DroplessMoE` is the second expert layer: token-choice top-k with no
capacity and no dropped token (OLMoE, arXiv:2409.02060). The dispatch tensor
above grows with tokens x experts x capacity and cannot reach a training
batch; here the token-expert assignments are sorted by expert, the three
expert products run as grouped matmuls over the sorted rows
(``jax.lax.ragged_dot``), and the un-sort and the weighted sum combine them.
It runs on one chip (or per batch shard); :class:`MoEMLP` stays the layer
that shards experts over a mesh until ROADMAP R1 gives this one an
all-to-all. Told which experts it holds (``experts_held``), it is one rank of
an expert-parallel layout without the exchange: it routes over all of its
``n_experts`` and computes what its own experts give.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry


class MoEMLP(nn.Module):
    """Switch-style top-1 MoE feed-forward: gate → dispatch → per-expert
    SwiGLU-free MLP (silu) → combine. ``(B, T, D)`` in and out.

    Pass ``comm=`` to shard the expert axis over the mesh (``n_experts``
    divisible by ``comm.size``); without it the layer is a single-shard
    reference implementation with identical numerics.
    """

    n_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    comm: Optional[Any] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n_tok = b * t
        xt = x.reshape(n_tok, d)

        logits = nn.Dense(
            self.n_experts, use_bias=False, dtype=self.dtype, name="gate"
        )(xt)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        expert = jnp.argmax(probs, axis=-1)  # (N,) top-1
        gate_w = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

        cap = int(math.ceil(n_tok / self.n_experts * self.capacity_factor))
        e_onehot_i = jax.nn.one_hot(expert, self.n_experts, dtype=jnp.int32)
        # 1-indexed arrival position of each token within its expert queue —
        # integer cumsum: an f32 one loses exact positions past 2^24 tokens
        pos = jnp.cumsum(e_onehot_i, axis=0) * e_onehot_i
        keep = (pos > 0) & (pos <= cap)
        pos0 = jnp.clip(pos - 1, 0, cap - 1)
        slot = jax.nn.one_hot(pos0, cap, dtype=jnp.float32)  # (N, E, C)
        dispatch = slot * keep[..., None].astype(jnp.float32)
        combine = dispatch * gate_w[:, None, None]

        w_in = self.param(
            "w_in",
            nn.initializers.lecun_normal(),
            (self.n_experts, d, self.d_ff),
            self.dtype,
        )
        w_out = self.param(
            "w_out",
            nn.initializers.lecun_normal(),
            (self.n_experts, self.d_ff, d),
            self.dtype,
        )

        expert_in = jnp.einsum("nd,nec->ecd", xt.astype(self.dtype), dispatch.astype(self.dtype))
        expert_in = self._shard_experts(expert_in)
        h = nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, self._shard_experts(w_in)))
        expert_out = jnp.einsum("ecf,efd->ecd", h, self._shard_experts(w_out))
        out = jnp.einsum("ecd,nec->nd", expert_out, combine.astype(self.dtype))
        return out.reshape(b, t, d)

    def _shard_experts(self, arr):
        if self.comm is None:
            return arr
        if self.n_experts % self.comm.size:
            raise ValueError(
                f"n_experts {self.n_experts} not divisible by mesh size "
                f"{self.comm.size}"
            )
        return jax.lax.with_sharding_constraint(
            arr, self.comm.sharding(0, arr.ndim)
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, order, inverse, k):
    """Row ``order[i] // k`` of ``xt`` for every sorted assignment ``i``.
    ``order`` is a permutation of the ``n * k`` assignments, so the
    transpose is the gather by ``inverse`` and a sum over each token's ``k``
    rows: no scatter in either direction."""
    return xt[order // k]


def _dispatch_fwd(xt, order, inverse, k):
    return xt[order // k], (order, inverse)


def _dispatch_bwd(k, res, g):
    _, inverse = res
    return g[inverse].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """``y`` back in assignment order (token-major): ``y[inverse]``, whose
    transpose is ``g[order]``."""
    return y[inverse]


def _unsort_fwd(y, order, inverse):
    return y[inverse], (order,)


def _unsort_bwd(res, g):
    return g[res[0]], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


class DroplessMoE(nn.Module):
    """Token-choice top-``k`` expert feed-forward without capacity:
    ``sum_j w_j * (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]`` over the ``k``
    experts with the largest router probability. ``(B, T, D)`` in and out.

    The router's product, its softmax and the top-k weights are float32;
    the expert products take ``dtype`` operands and give ``accum_dtype``
    results (``None``: ``dtype``). Every assignment is computed: the groups
    are as long as the routing makes them, nothing is padded to a worst
    case and nothing is dropped.

    Sown into the ``aux`` collection (``apply(..., mutable=["aux"])``):
    ``load_balance`` = ``E * sum_e f_e P_e`` with ``f_e`` the share of the
    ``N * k`` assignments that went to expert ``e`` and ``P_e`` the mean
    router probability of ``e``; ``router_z`` = ``mean(logsumexp(r)^2)``;
    ``expert_counts`` (int32, one entry an expert); ``computed`` (how many of
    the ``N * k`` sorted rows the grouped products put into the group of the
    expert that was chosen for them, :func:`rows_computed`); ``chosen`` (the
    experts of every token, ``(N, k)``) and ``weights`` (theirs). The top-k
    weights are the router's probabilities as they are (OLMoE's
    ``norm_topk_prob`` false) or, with ``norm_topk``, divided by their sum
    over the k chosen.

    ``shared_d_ff`` > 0 adds an expert of that width that every token takes,
    behind a sigmoid gate of its own: ``+ sigmoid(h w_s) * E_shared(h)``, or,
    with ``shared_gate`` false, as it is: ``+ E_shared(h)``.

    ``score="sigmoid"`` scores each expert on its own: ``s = sigmoid(r)`` in
    float32 in place of the softmax; the weights are the chosen ``s`` (with
    ``norm_topk`` over their sum + ``norm_topk_eps``), times ``route_scale``. The auxiliary
    terms stay defined: ``P_e`` is then the mean of ``s`` normalised to sum 1 a
    token, ``router_z`` as before over the logits. ``select_bias`` adds a bias
    ``b`` of one float32 an expert that only the *choice* sees: the top-k is
    taken of ``score + b``, the weights of the score without it, and no
    gradient reaches ``b``. It is no parameter: it lives in the collection
    ``route_bias`` beside ``params`` (zeros at ``init``), the optimizer never
    holds it, and a rule of its own moves it after a step
    (:func:`balance_bias_rule`, ``DataParallel.make_train_step(state_rule=)``).

    ``experts_held = (first, count)`` makes the layer one share of an
    expert-parallel layout: the router, its softmax, the top-k and its
    weights (normalised over all k chosen, held or not) and both auxiliary
    terms are over all ``n_experts``; the expert weights are those of experts
    ``first .. first + count - 1`` only, and only the assignments that fall on
    them are computed; the others add nothing. The shares of all ranks, with
    the shared expert counted once, add up to the whole layer. The work
    around the experts goes with the rows that land here: they are gathered,
    multiplied and summed back into their tokens in windows of the sorted
    assignments: the first, ``held_window`` (2) x an even share of the
    ``N * k`` assignments long, holds them all unless the routing is far from
    even, and then further windows of one even share run, behind a branch, as
    many as hold a row, until every held assignment is computed (none is
    dropped). Where the first window is longer than 3 even shares, a window is
    the length of its buffers, not of its work: the gather of its rows, the
    elementwise pass between the grouped products and the sum back into the
    tokens go over blocks of a quarter of an even share, as many as hold a
    held row (a trip count read from the routing), and the grouped products
    skip what lies past their last group, so a step's time follows the rows
    that land here and not the window (``held_window`` 5 for one even share
    of live rows: 555 ms a step with every pass over the window, 514 with
    the blocks: TPU v5e, my chip runs, PR 40, calls 1 and 2). A shorter
    window is moved whole, one pass each: a row costs 2 to 3 times as much
    in a block's gather and scatter-add as in a whole window's, so at 2
    shares, half of them live, the blocks lose (calls 4 and 5).
    A first window as long as the assignments themselves (``held_window >=
    n_experts / count``) leaves no further one and no branch.
    ``held`` (the assignments due here) and ``moved`` (the rows gone over, by
    blocks or by whole windows: 1.0 x ``held`` is movement that touches live
    rows only) are sown beside ``computed``.
    """

    n_experts: int
    top_k: int
    d_ff: int
    dtype: Any = jnp.float32
    accum_dtype: Optional[Any] = None
    norm_topk: bool = False
    shared_d_ff: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    matrix_init: Any = None  # None: lecun_normal, as every expert matrix was drawn before
    out_init: Any = None  # the matrices that write into the residual stream
    score: str = "softmax"  # or "sigmoid"
    route_scale: float = 1.0
    shared_gate: bool = True
    select_bias: bool = False
    norm_topk_eps: float = 1e-20  # added to the sum a sigmoid router's top-k weights are divided by
    held_window: float = 2.0  # the held experts' first window, in even shares of the assignments

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n, k, e = b * t, self.top_k, self.n_experts
        out_dtype = self.dtype if self.accum_dtype is None else self.accum_dtype
        xt = x.reshape(n, d)
        first, held = (0, e) if self.experts_held is None else self.experts_held
        if not (0 <= first and 0 < held and first + held <= e):
            raise ValueError(f"experts_held {self.experts_held} lies outside the {e} experts")
        lecun = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        matrix = nn.initializers.lecun_normal() if self.matrix_init is None else self.matrix_init
        init_in = lecun if self.matrix_init is None else self.matrix_init
        init_out = init_in if self.out_init is None else self.out_init

        with jax.named_scope("moe.route"):
            w_router = self.param("router", matrix, (d, e), jnp.float32)
            logits = jnp.dot(
                xt.astype(jnp.float32), w_router,
                precision=jax.lax.Precision.HIGHEST,
            )
            if self.score == "softmax":
                probs = scores = jax.nn.softmax(logits, axis=-1)
            elif self.score == "sigmoid":
                telemetry.get_registry().add("moe.route.sigmoid")
                scores = jax.nn.sigmoid(logits)
                probs = scores / jnp.sum(scores, axis=-1, keepdims=True)  # for the auxiliary terms
            else:
                raise ValueError(f"score must be 'softmax' or 'sigmoid', got {self.score!r}")
            if self.select_bias:
                bias = self.variable("route_bias", "bias", jnp.zeros, (e,), jnp.float32).value
                _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
                weights = jnp.take_along_axis(scores, chosen, axis=-1)
            else:
                weights, chosen = jax.lax.top_k(scores, k)  # (n, k), float32
            if self.norm_topk:
                total = jnp.sum(weights, axis=-1, keepdims=True)
                weights = weights / (total if self.score == "softmax" else total + self.norm_topk_eps)
            if self.route_scale != 1.0:
                weights = weights * self.route_scale
            flat = chosen.reshape(n * k).astype(jnp.int32)
            counts = jnp.sum(
                flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32
            )

        shape_in, shape_out = (held, d, self.d_ff), (held, self.d_ff, d)
        w_gate = self.param("w_gate", init_in, shape_in, jnp.float32)
        w_up = self.param("w_up", init_in, shape_in, jnp.float32)
        w_down = self.param("w_down", init_out, shape_out, jnp.float32)
        experts = tuple(w.astype(self.dtype) for w in (w_gate, w_up, w_down))

        sown = {}
        if self.experts_held is None:
            with jax.named_scope("moe.route"):
                ids = jnp.arange(n * k, dtype=jnp.int32)
                by_expert, order = jax.lax.sort_key_val(flat, ids)  # stable: by expert, then token
                _, inverse = jax.lax.sort_key_val(order, ids)
                rows = _dispatch(xt.astype(self.dtype), order, inverse, k)
            with jax.named_scope("moe.experts"):
                y = _swiglu(rows, counts, experts, out_dtype)
            with jax.named_scope("moe.combine"):
                y = _unsort(y, order, inverse).reshape(n, k, d)
                out = jnp.einsum("nk,nkd->nd", weights, y.astype(jnp.float32))
            computed = rows_computed(by_expert, counts)
        else:
            out, computed, sown["moved"] = _held_experts(
                xt.astype(self.dtype), flat, weights.reshape(n * k), counts[first:first + held],
                experts, first, e, k, out_dtype, self.held_window,
            )
            sown["held"] = jnp.sum(counts[first:first + held])

        if self.shared_d_ff:
            with jax.named_scope("moe.shared"):
                dense = lambda width, name, init: nn.Dense(  # noqa: E731
                    width, use_bias=False, dtype=self.dtype, name=name, kernel_init=init,
                    dot_general=functools.partial(jax.lax.dot_general, preferred_element_type=out_dtype),
                )
                hidden = nn.silu(dense(self.shared_d_ff, "shared_gate", matrix)(xt)) * dense(
                    self.shared_d_ff, "shared_up", matrix
                )(xt)
                y = dense(d, "shared_down", matrix if self.out_init is None else self.out_init)(hidden)
                if self.shared_gate:
                    w_shared = self.param("shared_router", matrix, (d, 1), jnp.float32)
                    gate = jax.nn.sigmoid(jnp.dot(
                        xt.astype(jnp.float32), w_shared, precision=jax.lax.Precision.HIGHEST,
                    ))
                    out = out + gate * y.astype(jnp.float32)
                else:
                    out = out + y.astype(jnp.float32)

        f = counts.astype(jnp.float32) / (n * k)
        self.sow("aux", "moe", {
            "load_balance": e * jnp.sum(f * probs.mean(axis=0)),
            "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "expert_counts": counts,
            "computed": computed,
            "chosen": chosen,
            "weights": weights,
            **sown,
        })
        return out.astype(x.dtype).reshape(b, t, d)


# A first window longer than this many even shares of the assignments is moved
# in blocks of an even share over LIVE_BLOCKS_A_SHARE (rounded up to 8 rows), as
# many blocks as hold a held row; a shorter one whole, in one pass. Both from
# readings on the chip: :func:`_held_experts`
BLOCKS_FROM_SHARES = 3
LIVE_BLOCKS_A_SHARE = 4


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_window(xt, tokens, n):
    """``xt[tokens]``, a window whole; its transpose sums the rows back into
    their tokens (``segment_sum``)."""
    return xt[tokens]


def _take_window_fwd(xt, tokens, n):
    return xt[tokens], tokens


def _take_window_bwd(n, tokens, g):
    return jax.ops.segment_sum(g, tokens, num_segments=n), None


_take_window.defvjp(_take_window_fwd, _take_window_bwd)


def _live_blocks(block, bound, live, body, start):
    """``body(first, at, carry)`` over the blocks of ``block`` rows that hold
    one of a window's ``live`` first rows: a loop whose trip count is read from
    the routing. A block covers rows ``at .. at + block - 1``; ``at`` is
    ``first = j * block`` but in the last block of a window that is no whole
    number of them, which lies back over the one before (to write such a row
    again writes the same; a sum takes the rows from ``first`` on)."""

    def step(j, carry):
        return body(j * block, jnp.minimum(j * block, bound - block), carry)

    return jax.lax.fori_loop(jnp.zeros((), jnp.int32), -(-live // block), step, start)


def _sum_live(block, start, y, weights, tokens, live):
    """``start`` ``(n, d)`` float32 with the rows ``i < live`` of ``y`` (times
    ``weights[i]``, where given) added into its rows ``tokens[i]``, a block at a
    time in place; no row of ``y`` from ``live`` on is read into the sum (it
    is selected out, never multiplied by a zero)."""
    bound = tokens.shape[0]

    def body(first, at, acc):
        at_rows = at + jnp.arange(block, dtype=jnp.int32)
        rows = jax.lax.dynamic_slice_in_dim(y, at, block).astype(jnp.float32)
        if weights is not None:
            rows = rows * jax.lax.dynamic_slice_in_dim(weights, at, block)[:, None]
        rows = jnp.where(((at_rows >= first) & (at_rows < live))[:, None], rows, 0)
        return acc.at[jax.lax.dynamic_slice_in_dim(tokens, at, block)].add(rows)

    return _live_blocks(block, bound, live, body, start)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _take_rows(block, n, xt, tokens, live):
    """``xt[tokens[i]]`` for the rows ``i < live`` and zeros from there on,
    gathered a block at a time into a buffer of ``len(tokens)`` rows; its
    transpose sums the live rows back into their ``n`` tokens, as
    :func:`_sum_rows` does forward."""
    bound, d = tokens.shape[0], xt.shape[1]

    def body(first, at, rows):
        keep = at + jnp.arange(block, dtype=jnp.int32) < live
        got = jnp.where(keep[:, None], xt[jax.lax.dynamic_slice_in_dim(tokens, at, block)], 0)
        return jax.lax.dynamic_update_slice_in_dim(rows, got, at, 0)

    return _live_blocks(block, bound, live, body, jnp.zeros((bound, d), xt.dtype))


def _take_rows_fwd(block, n, xt, tokens, live):
    return _take_rows(block, n, xt, tokens, live), (tokens, live)


def _take_rows_bwd(block, n, res, g):
    start = jnp.zeros((n, g.shape[1]), jnp.float32)
    return _sum_live(block, start, g, None, *res).astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sum_rows(block, start, y, weights, tokens, live):
    """``start + sum_i weights[i] * y[i]`` into row ``tokens[i]`` (``(n, d)``
    float32) over the rows ``i < live`` (:func:`_sum_live`); its transpose
    gathers the cotangent's rows a block at a time, as :func:`_take_rows` does
    forward, and forms the weights' gradient (a row dot) in the same loop."""
    return _sum_live(block, start, y, weights, tokens, live)


def _sum_rows_fwd(block, start, y, weights, tokens, live):
    return _sum_rows(block, start, y, weights, tokens, live), (y, weights, tokens, live)


def _sum_rows_bwd(block, res, g):
    y, weights, tokens, live = res

    def body(first, at, carry):
        keep = at + jnp.arange(block, dtype=jnp.int32) < live
        got = g[jax.lax.dynamic_slice_in_dim(tokens, at, block)]
        rows = jax.lax.dynamic_slice_in_dim(y, at, block).astype(jnp.float32)
        w = jax.lax.dynamic_slice_in_dim(weights, at, block)
        dy = jnp.where(keep[:, None], w[:, None] * got, 0).astype(y.dtype)
        dw = jnp.where(keep, jnp.sum(rows * got, axis=-1), 0).astype(weights.dtype)
        return tuple(jax.lax.dynamic_update_slice_in_dim(whole, part, at, 0) for whole, part in zip(carry, (dy, dw)))

    dy, dw = _live_blocks(block, y.shape[0], live, body, (jnp.zeros_like(y), jnp.zeros_like(weights)))
    return g, dy, dw, None, None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def _gate(gate, up, dtype):
    return (nn.silu(gate) * up).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gate_rows(block, dtype, gate, up, live):
    """``silu(gate) * up`` as ``dtype`` over the blocks that hold one of the
    ``live`` first rows; zeros in the blocks past them (no grouped product
    reads a row from ``live`` on, whatever it holds)."""

    def body(first, at, hidden):
        got = _gate(*(jax.lax.dynamic_slice_in_dim(a, at, block) for a in (gate, up)), dtype)
        return jax.lax.dynamic_update_slice_in_dim(hidden, got, at, 0)

    return _live_blocks(block, gate.shape[0], live, body, jnp.zeros(gate.shape, dtype))


def _gate_rows_fwd(block, dtype, gate, up, live):
    return _gate_rows(block, dtype, gate, up, live), (gate, up, live)


def _gate_rows_bwd(block, dtype, res, g):
    gate, up, live = res

    def body(first, at, carry):
        here = lambda a: jax.lax.dynamic_slice_in_dim(a, at, block)  # noqa: E731
        got = jax.vjp(functools.partial(_gate, dtype=dtype), here(gate), here(up))[1](here(g))
        return tuple(jax.lax.dynamic_update_slice_in_dim(whole, part, at, 0) for whole, part in zip(carry, got))

    return (*_live_blocks(block, gate.shape[0], live, body, (jnp.zeros_like(gate), jnp.zeros_like(up))), None)


_gate_rows.defvjp(_gate_rows_fwd, _gate_rows_bwd)


def _swiglu(rows, sizes, experts, out_dtype, live_blocks=None):
    """``(silu(rows Wg) * (rows Wu)) Wd`` as three grouped products over
    ``sizes`` rows an expert; ``experts`` = ``(Wg, Wu, Wd)`` in the operands'
    dtype. ``live_blocks = (block, live)``: the elementwise pass between the
    products goes over the blocks of the ``live`` first rows alone."""
    grouped = functools.partial(jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=out_dtype)
    gate, up = grouped(rows, experts[0]), grouped(rows, experts[1])
    if live_blocks is None:
        hidden = (nn.silu(gate) * up).astype(rows.dtype)
    else:
        hidden = _gate_rows(live_blocks[0], rows.dtype, gate, up, live_blocks[1])
    return grouped(hidden, experts[2])


def _window(static, diff, ints, lo, start):
    """The window of ``bound`` sorted held rows from row ``lo``: gather them,
    the grouped products over the part of every expert's group that lies in
    the window, and the weighted sum back into the rows' tokens, added to
    ``start``. With a ``block``, the gather, the pass between the products and
    the sum go over the blocks of that many rows that hold a held row; without
    (``None``), over the window in one pass each, whatever lies in it.
    ``(start + the window's part (n, d) float32, int32 (rows computed, rows moved))``."""
    bound, block, k, out_dtype = static
    xt, experts, sorted_weights = diff
    order, by_expert, starts, ends = ints
    n = xt.shape[0]
    # a grouped product leaves the rows past its last group as they lay in
    # memory (on the chip; the CPU zeroes them), forward and backward: both
    # ways those rows are selected out or never read, never multiplied by a zero
    with jax.named_scope("moe.route"):
        tokens = jax.lax.dynamic_slice_in_dim(order, lo, bound) // k
        sizes = jnp.clip(ends, lo, lo + bound) - jnp.clip(starts, lo, lo + bound)
    weights = functools.partial(jax.lax.dynamic_slice_in_dim, sorted_weights, lo, bound)
    if block is None:
        live = lo + jnp.arange(bound, dtype=jnp.int32) < ends[-1]
        with jax.named_scope("moe.route"):
            rows = jnp.where(live[:, None], _take_window(xt, tokens, n), 0)
        with jax.named_scope("moe.experts"):
            y = jnp.where(live[:, None], _swiglu(rows, sizes, experts, out_dtype), 0)
        with jax.named_scope("moe.combine"):
            weighted = jnp.where(live, weights(), 0.0)[:, None] * y.astype(jnp.float32)
            out = start + jax.ops.segment_sum(weighted, tokens, num_segments=n)
        moved = jnp.full((), bound, jnp.int32)
    else:
        block = min(block, bound)
        live = jnp.clip(ends[-1] - lo, 0, bound)
        with jax.named_scope("moe.route"):
            rows = _take_rows(block, n, xt, tokens, live)
        with jax.named_scope("moe.experts"):
            y = _swiglu(rows, sizes, experts, out_dtype, (block, live))
        with jax.named_scope("moe.combine"):
            out = _sum_rows(block, start, y, weights(), tokens, live)
        moved = -(-live // block) * block
    computed = rows_computed(jax.lax.dynamic_slice_in_dim(by_expert, lo, bound), sizes)
    return out, jnp.stack([computed, moved])


def _live_further(static, ints):
    """How many further windows hold a held row."""
    first, bound = static[:2]
    return -(-jnp.maximum(ints[3][-1] - first, 0) // bound)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _further_windows(static, diff, ints):
    """The sum of the windows past the first (``static``: the first's length,
    theirs, a block's, ``k``, the products' dtype): a loop over as many of them as hold a
    held row, so that what a step pays for an uneven routing goes with the
    rows that overflow, a window of one even share at a time. The backward pass
    goes over the same windows again and keeps nothing a window: a loop's own
    transpose would keep every window's operands, the expert weights among
    them."""
    first, bound = static[:2]
    n, d = diff[0].shape

    def body(j, carry):
        out, done = _window(static[1:], diff, ints, first + j * bound, carry[0])
        return out, carry[1] + done

    start = (jnp.zeros((n, d), jnp.float32), jnp.zeros((2,), jnp.int32))
    return jax.lax.fori_loop(jnp.zeros((), jnp.int32), _live_further(static, ints), body, start)


def _further_windows_fwd(static, diff, ints):
    return _further_windows(static, diff, ints), (diff, ints)


def _further_windows_bwd(static, res, g):
    first, bound = static[:2]
    diff, ints = res

    def body(j, acc):
        window = lambda operands: _window(static[1:], operands, ints, first + j * bound, jnp.zeros_like(g[0]))[0]  # noqa: E731
        return jax.tree.map(lambda a, got: a + got.astype(jnp.float32), acc, jax.vjp(window, diff)[1](g[0])[0])

    acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), diff)
    acc = jax.lax.fori_loop(jnp.zeros((), jnp.int32), _live_further(static, ints), body, acc)
    return jax.tree.map(lambda a, like: a.astype(like.dtype), acc, diff), None


_further_windows.defvjp(_further_windows_fwd, _further_windows_bwd)


def _held_experts(xt, flat, weights, held_counts, experts, first, e, k, out_dtype, window=2.0):
    """The part of an expert layer that its held experts (``experts``: their
    three weights, ``first`` the id of the first) give: ``(out (n, d) float32,
    computed, moved)``. The ``n * k`` assignments are sorted with the held ones
    first, by expert and then by token (int32 keys); then windows of sorted
    rows are gathered, multiplied and summed back into their tokens
    (:func:`_window`). The first window is ``window`` (2) x an even share of
    the assignments long; the rows past it, if the routing leaves any, go a
    window of one even share at a time, behind one ``lax.cond``, as many
    windows as hold a row (:func:`_further_windows`), and the first window adds
    its rows onto what they gave. **A first window longer than
    ``BLOCKS_FROM_SHARES`` (3) even shares goes by blocks**: its length is what
    its buffers hold and the grouped products are handed, and what is done
    round the products goes by blocks of ``even / LIVE_BLOCKS_A_SHARE`` rows,
    as many as hold a held row, so a long window costs its zero fills and
    little more: with 5 even shares for one of live rows (LFM2-24B-A2B's
    cell, 40,960 rows for ~8,000) a step took 555.2-556.5 ms with every pass
    over the window and 512.9-516.2 by blocks; blocks of a half, a quarter and
    an eighth of a share read 523.6-528.8, 514.8-518.5 and 514.0-517.2 before
    the sums ran onto one another, and a quarter and an eighth 512.9-516.2
    and 513.3-516.5 after: a quarter it is (TPU v5e, three seeds of 30 steps,
    my chip runs, PR 40, calls 1 and 2). What such a window still pays by its
    length: the zero fills of its buffers (~16 ms a step there) and, in the
    grouped products' own transposes, the casts and the sum of the row
    gradients (~11 ms). **A shorter first window is moved whole**, one gather,
    select and ``segment_sum`` each, whatever lies in it: a row of a block
    costs ~245 ns in the loop's scatter-add and ~65 in its gather where a
    whole window's cost ~91 and ~33 (XLA sorts a whole scatter's indices; a
    sort a block, tried, costs more than it gives), so blocks pay from about
    3 window rows a live one: at 2 even shares with one live, by blocks,
    Trinity-Mini's step read +0.0 to +0.6% and Qwen3-Next's +2.5 to +2.9%
    (calls 4 and 5); at 3, in the LFM2 cell's shapes, 499.0-502.1 ms by
    blocks for 510.9-511.9 whole, and at 4 by blocks 506.2-508.6 (two seeds
    of 20 steps, call 9): there the two forms cross near 2.5 shares, in
    Qwen3-Next's shapes past 3, and no cell runs a window between 2 and 5.
    ``moved`` counts the rows gone over either way. A further
    window still runs its three products again backward and carries the
    experts' float32 gradients (~10 ms for rows worth ~5: PR 32, call 10),
    which is why the first one is sized to hold the routing a cell sees."""
    n = xt.shape[0]
    held = held_counts.shape[0]
    even = -(-n * k * held // e)
    bound = min(n * k, math.ceil(window * even / 8) * 8)
    more = min(n * k - bound, -(-even // 8) * 8)  # a further window's rows
    with jax.named_scope("moe.route"):
        local = flat - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held + 1)  # past every group: a row that rows_computed never counts
        ids = jnp.arange(n * k, dtype=jnp.int32)
        by_expert, order = jax.lax.sort_key_val(key, ids)  # stable: held first, by expert, then token
        _, inverse = jax.lax.sort_key_val(order, ids)
        sorted_weights = _unsort(weights, inverse, order)  # weights[order]; transposed by a gather too
        ends = jnp.cumsum(held_counts)
    block = -(-even // (8 * LIVE_BLOCKS_A_SHARE)) * 8 if bound > BLOCKS_FROM_SHARES * even else None
    static, diff, ints = (bound, block, k, out_dtype), (xt, experts, sorted_weights), (order, by_expert, ends - held_counts, ends)
    out, counted = jnp.zeros(xt.shape, jnp.float32), jnp.zeros((2,), jnp.int32)
    if more:
        def further():
            # whole windows to the end of the sorted rows: what is added holds no held row
            pad = -(n * k - bound) % more
            padded = lambda a, fill: jnp.pad(a, (0, pad), constant_values=fill)  # noqa: E731
            return _further_windows(
                (bound, more, block, k, out_dtype), (xt, experts, padded(sorted_weights, 0.0)),
                (padded(order, 0), padded(by_expert, held + 1), ints[2], ends),
            )

        out, counted = jax.lax.cond(ends[-1] > bound, further, lambda: (out, counted))
    out, here = _window(static, diff, ints, 0, out)  # onto what the further windows gave
    return out, *(counted + here)


def rows_computed(by_expert, group_sizes):
    """How many of the sorted rows a grouped product over ``group_sizes``
    computes with the expert chosen for them. ``by_expert[i]`` is the expert
    of sorted row ``i``; the grouped product gives row ``i`` to the group
    whose span of ``cumsum(group_sizes)`` holds ``i``, and to none (a row of
    zeros) past the last span. Sizes that a capacity cut short, or an order
    that is not by expert, leave rows with another expert or with none: the
    assignments a routing dropped."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(by_expert.shape[0], dtype=ends.dtype)
    group = jnp.sum(rows[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)  # len(group_sizes): none
    return jnp.sum(group == by_expert, dtype=jnp.int32)


def balance_bias_rule(rate: float):
    """``rule(state, aux) -> state`` for ``make_train_step(state_rule=)``: the
    balance without an auxiliary loss. After a step, in every expert layer,
    ``b_e += rate * sign(mean_e'(c_e') - c_e)`` with ``c`` the step's own
    assignment counts over all experts (``aux["expert_counts"]``, layers x
    experts, in the order of the blocks): an expert that took more than the
    mean is chosen a little less readily at the next step. ``state`` is what
    the step carries beside ``params``; its ``route_bias`` collection holds one
    ``bias`` an expert layer (``DroplessMoE(select_bias=True)``)."""

    def rule(state, aux):
        counts = aux["expert_counts"].astype(jnp.float32)
        blocks = sorted(state["route_bias"], key=lambda name: int(name[len("block"):]))
        if len(blocks) != counts.shape[0]:
            raise ValueError(f"{len(blocks)} biases for {counts.shape[0]} expert layers")
        moved = {
            name: {"moe": {"bias": state["route_bias"][name]["moe"]["bias"] + rate * jnp.sign(
                jnp.mean(counts[j]) - counts[j]
            )}}
            for j, name in enumerate(blocks)
        }
        return {**state, "route_bias": moved}

    return rule


def record_routing(aux) -> None:
    """Count one step's routing in the telemetry registry from the host copy
    of a loss's auxiliary outputs (:func:`heat_tpu.nn.causal_lm_loss`):
    ``moe.assignments`` (what the routing chose and this program was due to
    compute: all of them, or those on the experts it holds), ``moe.dropped``
    (those of them that the grouped products did not compute with the chosen
    expert: ``assignments_due - assignments_computed``, 0 for the dropless
    layer), ``moe.steps``, and ``moe.load_max_over_mean`` summed over the
    steps (the busiest expert's count over the mean count, worst layer, over
    all experts), and where the loss gives it ``moe.route_bias_max_abs`` (a
    high-water mark: the largest magnitude a selection bias has reached).
    Where the layers hold a share of their experts, also
    ``moe.held_assignments`` (the step's assignments on held experts),
    ``moe.held_share`` (that over tokens x top-k x layers, summed over the
    steps: 1/16 a step for an even routing over sixteen shares) and
    ``moe.held_rows_moved`` (the rows gone over round the held experts: by
    blocks ``held_assignments`` and what a window's last block holds past its
    last live row, by whole windows their lengths). Aux without
    ``expert_counts`` (a dense model) counts nothing."""
    if not (isinstance(aux, dict) and "expert_counts" in aux):
        return
    counts = np.asarray(aux["expert_counts"], dtype=np.float64)  # (layers, experts)
    reg = telemetry.get_registry()
    reg.add("moe.steps", 1)
    reg.add("moe.assignments", float(aux["assignments_due"]))
    reg.add("moe.dropped", float(aux["assignments_due"]) - float(aux["assignments_computed"]))
    reg.add("moe.load_max_over_mean", float((counts.max(axis=-1) / counts.mean(axis=-1)).max()))
    if "route_bias_max_abs" in aux:
        reg.high_water("moe.route_bias_max_abs", float(aux["route_bias_max_abs"]))
    if "assignments_routed" in aux:
        reg.add("moe.held_assignments", float(aux["assignments_due"]))
        reg.add("moe.held_share", float(aux["assignments_due"]) / float(aux["assignments_routed"]))
        reg.add("moe.held_rows_moved", float(aux["rows_moved"]))


def read_routing(loss, aux):
    """A train step's loss and auxiliary outputs on the host (numpy), read in
    one transfer under the span ``heat_tpu.train.step.readback``, where the
    step's routing is counted (:func:`record_routing`): the loop that reads
    its loss every step pays no second transfer for the counters.
    ``make_train_step(..., has_aux=True)`` returns both on the device."""
    with telemetry.span("heat_tpu.train.step.readback"):
        loss, aux = jax.device_get((loss, aux))
        record_routing(aux)
    return loss, aux
