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
    multiplied and summed back into their tokens in windows: the first,
    ``held_window`` (2) x an even share of the ``N * k`` assignments long, holds
    them all unless the routing is far from even, and then further windows of
    one even share run, behind a branch, as many as hold a row, until every
    held assignment is computed (none is dropped). A first window as long as
    the assignments themselves (``held_window >= n_experts / count``) leaves
    no further one: the layer's time then does not follow the routing.
    ``held`` (the assignments due here) is sown beside ``computed``.
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
            out, computed = _held_experts(
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(xt, tokens, n):
    """``xt[tokens]``; its transpose sums the rows back into their tokens
    (``segment_sum``), as :func:`_sum_rows` does forward."""
    return xt[tokens]


def _take_rows_fwd(xt, tokens, n):
    return xt[tokens], tokens


def _take_rows_bwd(n, tokens, g):
    return jax.ops.segment_sum(g, tokens, num_segments=n), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _swiglu(rows, sizes, experts, out_dtype):
    """``(silu(rows Wg) * (rows Wu)) Wd`` as three grouped products over
    ``sizes`` rows an expert; ``experts`` = ``(Wg, Wu, Wd)`` in the operands'
    dtype."""
    grouped = functools.partial(jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=out_dtype)
    hidden = (nn.silu(grouped(rows, experts[0])) * grouped(rows, experts[1])).astype(rows.dtype)
    return grouped(hidden, experts[2])


def _window(static, diff, ints, lo):
    """The window of ``bound`` sorted held rows from row ``lo``: gather them,
    the grouped products over the part of every expert's group that lies in
    the window, and the weighted sum back into the rows' tokens. ``(part (n, d)
    float32, rows computed)``."""
    bound, k, out_dtype = static
    xt, experts, sorted_weights = diff
    order, by_expert, starts, ends = ints
    n = xt.shape[0]
    with jax.named_scope("moe.route"):
        tokens = jax.lax.dynamic_slice_in_dim(order, lo, bound) // k
        live = lo + jnp.arange(bound, dtype=jnp.int32) < ends[-1]
        sizes = jnp.clip(ends, lo, lo + bound) - jnp.clip(starts, lo, lo + bound)
        # a grouped product leaves the rows past its last group as they lay in
        # memory (on the chip; the CPU zeroes them), forward and backward:
        # both ways those rows are selected out, never multiplied by a zero
        rows = jnp.where(live[:, None], _take_rows(xt, tokens, n), 0)
    with jax.named_scope("moe.experts"):
        y = jnp.where(live[:, None], _swiglu(rows, sizes, experts, out_dtype), 0)
    with jax.named_scope("moe.combine"):
        w = jnp.where(live, jax.lax.dynamic_slice_in_dim(sorted_weights, lo, bound), 0.0)
        part = jax.ops.segment_sum(w[:, None] * y.astype(jnp.float32), tokens, num_segments=n)
    return part, rows_computed(jax.lax.dynamic_slice_in_dim(by_expert, lo, bound), sizes)


def _live_further(static, ints):
    """How many further windows hold a held row."""
    first, bound = static[:2]
    return -(-jnp.maximum(ints[3][-1] - first, 0) // bound)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _further_windows(static, diff, ints):
    """The sum of the windows past the first (``static``: the first's length,
    theirs, ``k``, the products' dtype): a loop over as many of them as hold a
    held row, so that what a step pays for an uneven routing goes with the
    rows that overflow, a window of one even share at a time. The backward pass
    goes over the same windows again and keeps nothing a window: a loop's own
    transpose would keep every window's operands, the expert weights among
    them."""
    first, bound = static[:2]
    n, d = diff[0].shape

    def body(j, carry):
        part, done = _window(static[1:], diff, ints, first + j * bound)
        return carry[0] + part, carry[1] + done

    start = (jnp.zeros((n, d), jnp.float32), jnp.zeros((), jnp.int32))
    return jax.lax.fori_loop(jnp.zeros((), jnp.int32), _live_further(static, ints), body, start)


def _further_windows_fwd(static, diff, ints):
    return _further_windows(static, diff, ints), (diff, ints)


def _further_windows_bwd(static, res, g):
    first, bound = static[:2]
    diff, ints = res

    def body(j, acc):
        _, transpose = jax.vjp(lambda operands: _window(static[1:], operands, ints, first + j * bound)[0], diff)
        return jax.tree.map(lambda a, got: a + got.astype(jnp.float32), acc, transpose(g[0])[0])

    acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), diff)
    acc = jax.lax.fori_loop(jnp.zeros((), jnp.int32), _live_further(static, ints), body, acc)
    return jax.tree.map(lambda a, like: a.astype(like.dtype), acc, diff), None


_further_windows.defvjp(_further_windows_fwd, _further_windows_bwd)


def _held_experts(xt, flat, weights, held_counts, experts, first, e, k, out_dtype, window=2.0):
    """The part of an expert layer that its held experts (``experts``: their
    three weights, ``first`` the id of the first) give: ``(out (n, d) float32,
    computed)``. The ``n * k`` assignments are sorted with the held ones
    first, by expert and then by token (int32 keys); then windows of sorted
    rows are gathered, multiplied and summed back into their tokens. The first
    window, ``window`` (2) x an even share of the assignments, always runs, and costs its
    whole length whatever lies in it; the rows past it, if the routing leaves
    any, go a window of one even share at a time, behind one ``lax.cond``, as
    many windows as hold a row (:func:`_further_windows`). The first window is
    no longer than that because every step pays for it (at 4 x, 46 ms of a 1,100
    ms step of 8 of 128 experts: TPU v5e, my chip runs, PR 32, call 8), and the
    further ones go by the row because a share of few experts under a routing
    far from even passes 2 x in many steps (a layer's share read 0.16 to 2.96 x
    even over ten seeds there, and a further window ~10 ms: call 10). A share
    whose routing drifts past 2 x inside a run's first steps is given a longer
    one (LFM2-24B-A2B's cell: 5 x, 65 ms of a 556 ms step, PR 39)."""
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
    static, diff, ints = (bound, k, out_dtype), (xt, experts, sorted_weights), (order, by_expert, ends - held_counts, ends)
    out, computed = _window(static, diff, ints, 0)
    if more:
        def further():
            # whole windows to the end of the sorted rows: what is added holds no held row
            pad = -(n * k - bound) % more
            padded = lambda a, fill: jnp.pad(a, (0, pad), constant_values=fill)  # noqa: E731
            return _further_windows(
                (bound, more, k, out_dtype), (xt, experts, padded(sorted_weights, 0.0)),
                (padded(order, 0), padded(by_expert, held + 1), ints[2], ends),
            )

        more_out, done = jax.lax.cond(
            ends[-1] > bound, further, lambda: (jnp.zeros_like(out), jnp.zeros((), jnp.int32))
        )
        out, computed = out + more_out, computed + done
    return out, computed


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
    ``moe.held_assignments`` (the step's assignments on held experts) and
    ``moe.held_share`` (that over tokens x top-k x layers, summed over the
    steps: 1/16 a step for an even routing over sixteen shares). Aux without
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
