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
all-to-all.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

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
    experts of every token, ``(N, k)``). The top-k weights are the router's
    probabilities as they are, not renormalised (OLMoE's ``norm_topk_prob``
    false).
    """

    n_experts: int
    top_k: int
    d_ff: int
    dtype: Any = jnp.float32
    accum_dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n, k, e = b * t, self.top_k, self.n_experts
        out_dtype = self.dtype if self.accum_dtype is None else self.accum_dtype
        xt = x.reshape(n, d)

        with jax.named_scope("moe.route"):
            w_router = self.param(
                "router", nn.initializers.lecun_normal(), (d, e), jnp.float32
            )
            logits = jnp.dot(
                xt.astype(jnp.float32), w_router,
                precision=jax.lax.Precision.HIGHEST,
            )
            probs = jax.nn.softmax(logits, axis=-1)
            weights, chosen = jax.lax.top_k(probs, k)  # (n, k), float32
            flat = chosen.reshape(n * k).astype(jnp.int32)
            ids = jnp.arange(n * k, dtype=jnp.int32)
            by_expert, order = jax.lax.sort_key_val(flat, ids)  # stable: by expert, then token
            _, inverse = jax.lax.sort_key_val(order, ids)
            counts = jnp.sum(
                flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32
            )
            rows = _dispatch(xt.astype(self.dtype), order, inverse, k)

        shape_in, shape_out = (e, d, self.d_ff), (e, self.d_ff, d)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", init, shape_in, jnp.float32)
        w_up = self.param("w_up", init, shape_in, jnp.float32)
        w_down = self.param("w_down", init, shape_out, jnp.float32)
        grouped = functools.partial(
            jax.lax.ragged_dot, group_sizes=counts, preferred_element_type=out_dtype
        )
        with jax.named_scope("moe.experts"):
            gate = grouped(rows, w_gate.astype(self.dtype))
            up = grouped(rows, w_up.astype(self.dtype))
            hidden = (nn.silu(gate) * up).astype(self.dtype)
            y = grouped(hidden, w_down.astype(self.dtype))

        with jax.named_scope("moe.combine"):
            y = _unsort(y, order, inverse).reshape(n, k, d)
            out = jnp.einsum("nk,nkd->nd", weights, y.astype(jnp.float32))

        f = counts.astype(jnp.float32) / (n * k)
        self.sow("aux", "moe", {
            "load_balance": e * jnp.sum(f * probs.mean(axis=0)),
            "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "expert_counts": counts,
            "computed": rows_computed(by_expert, counts),
            "chosen": chosen,
        })
        return out.astype(x.dtype).reshape(b, t, d)


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
    ``moe.assignments`` (what the routing chose), ``moe.dropped`` (those of
    them that the grouped products did not compute with the chosen expert:
    ``assignments_due - assignments_computed``, 0 for the dropless layer),
    ``moe.steps``, and ``moe.load_max_over_mean`` summed over the steps (the
    busiest expert's count over the mean count, worst layer). Aux without
    ``expert_counts`` (a dense model) counts nothing."""
    if not (isinstance(aux, dict) and "expert_counts" in aux):
        return
    counts = np.asarray(aux["expert_counts"], dtype=np.float64)  # (layers, experts)
    reg = telemetry.get_registry()
    reg.add("moe.steps", 1)
    reg.add("moe.assignments", float(aux["assignments_due"]))
    reg.add("moe.dropped", float(aux["assignments_due"]) - float(aux["assignments_computed"]))
    reg.add("moe.load_max_over_mean", float((counts.max(axis=-1) / counts.mean(axis=-1)).max()))


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
