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
    behind a sigmoid gate of its own: ``+ sigmoid(h w_s) * E_shared(h)``.

    ``experts_held = (first, count)`` makes the layer one share of an
    expert-parallel layout: the router, its softmax, the top-k and its
    weights (normalised over all k chosen, held or not) and both auxiliary
    terms are over all ``n_experts``; the expert weights are those of experts
    ``first .. first + count - 1`` only, and only the assignments that fall on
    them are computed; the others add nothing. The shares of all ranks, with
    the shared expert counted once, add up to the whole layer. The work
    around the experts goes with the rows that land here: they are gathered,
    multiplied and summed back into their tokens in windows of ``2 x`` an
    even share of the ``N * k`` assignments; one window holds them all unless
    the routing is far from even, and then further windows run, behind a
    branch, until every held assignment is computed (none is dropped).
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
            probs = jax.nn.softmax(logits, axis=-1)
            weights, chosen = jax.lax.top_k(probs, k)  # (n, k), float32
            if self.norm_topk:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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
                experts, first, e, k, out_dtype,
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
                w_shared = self.param("shared_router", matrix, (d, 1), jnp.float32)
                gate = jax.nn.sigmoid(jnp.dot(
                    xt.astype(jnp.float32), w_shared, precision=jax.lax.Precision.HIGHEST,
                ))
                out = out + gate * y.astype(jnp.float32)

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


def _window(static, diff, ints, i):
    """Window ``i`` of the sorted held rows: gather ``bound`` rows, the grouped
    products over the part of every expert's group that lies in the window,
    and the weighted sum back into the rows' tokens. ``(part (n, d) float32,
    rows computed)``."""
    bound, k, out_dtype = static
    xt, experts, sorted_weights = diff
    order, by_expert, starts, ends = ints
    n = xt.shape[0]
    lo = i * bound
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _further_windows(static, windows, diff, ints):
    """The sum of windows ``1 .. windows - 1``, each behind a branch that
    skips it when no held row is left for it. The backward pass goes over the
    windows again and keeps nothing a window: a scan's own transpose would
    keep every window's operands, the expert weights among them."""
    n, d = diff[0].shape

    def body(carry, i):
        part, done = jax.lax.cond(
            i * static[0] < ints[3][-1], lambda: _window(static, diff, ints, i),
            lambda: (jnp.zeros((n, d), jnp.float32), jnp.zeros((), jnp.int32)),
        )
        return (carry[0] + part, carry[1] + done), None

    start = (jnp.zeros((n, d), jnp.float32), jnp.zeros((), jnp.int32))
    return jax.lax.scan(body, start, jnp.arange(1, windows, dtype=jnp.int32))[0]


def _further_windows_fwd(static, windows, diff, ints):
    return _further_windows(static, windows, diff, ints), (diff, ints)


def _further_windows_bwd(static, windows, res, g):
    diff, ints = res
    wide = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)  # noqa: E731

    def body(acc, i):
        def live():
            _, transpose = jax.vjp(lambda operands: _window(static, operands, ints, i)[0], diff)
            return jax.tree.map(lambda a: a.astype(jnp.float32), transpose(g[0])[0])

        got = jax.lax.cond(i * static[0] < ints[3][-1], live, lambda: wide(diff))
        return jax.tree.map(jnp.add, acc, got), None

    acc = jax.lax.scan(body, wide(diff), jnp.arange(1, windows, dtype=jnp.int32))[0]
    return jax.tree.map(lambda a, like: a.astype(like.dtype), acc, diff), None


_further_windows.defvjp(_further_windows_fwd, _further_windows_bwd)


def _held_experts(xt, flat, weights, held_counts, experts, first, e, k, out_dtype):
    """The part of an expert layer that its held experts (``experts``: their
    three weights, ``first`` the id of the first) give: ``(out (n, d) float32,
    computed)``. The ``n * k`` assignments are sorted with the held ones
    first, by expert and then by token (int32 keys); then windows of ``bound``
    sorted rows (2 x an even share) are gathered, multiplied and summed back
    into their tokens. The first window always runs; the others only where
    held rows are left, behind one ``lax.cond``."""
    n = xt.shape[0]
    held = held_counts.shape[0]
    bound = min(n * k, -(-2 * (-(-n * k * held // e)) // 8) * 8)
    windows = -(-n * k // bound)
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
    if windows > 1:
        more, done = jax.lax.cond(
            ends[-1] > bound, lambda: _further_windows(static, windows, diff, ints),
            lambda: (jnp.zeros_like(out), jnp.zeros((), jnp.int32)),
        )
        out, computed = out + more, computed + done
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


def record_routing(aux) -> None:
    """Count one step's routing in the telemetry registry from the host copy
    of a loss's auxiliary outputs (:func:`heat_tpu.nn.causal_lm_loss`):
    ``moe.assignments`` (what the routing chose and this program was due to
    compute: all of them, or those on the experts it holds), ``moe.dropped``
    (those of them that the grouped products did not compute with the chosen
    expert: ``assignments_due - assignments_computed``, 0 for the dropless
    layer), ``moe.steps``, and ``moe.load_max_over_mean`` summed over the
    steps (the busiest expert's count over the mean count, worst layer, over
    all experts). Where the layers hold a share of their experts, also
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
