"""Pipeline parallelism (pp) over the mesh axis.

The reference implements data parallelism only (SURVEY §2.5: "no pipeline
parallelism"); this is the TPU-native strategy built on the same mesh
machinery, in two layers (ISSUE 19):

* :func:`pipeline_apply` — the historical flat GPipe forward: stages live
  one-per-mesh-position (their params stacked with a leading stage dim
  sharded over the axis), microbatch activations hop stage→stage with
  `ppermute`, and the whole ``S + M - 1``-tick wave is one `lax.fori_loop`
  inside one `shard_map`, cached at program-cache site ``pipeline.apply``
  (stage compute on inactive warmup/drain ticks is guarded by `lax.cond`,
  not computed-and-discarded). Differentiable end to end.

* the schedule-table-driven MPMD kernel (site ``pipeline.step``) behind
  :class:`heat_tpu.nn.Pipeline` — stages map onto `core/topology.py`
  node groups (:class:`~.schedule.StageMapping`), the ``local`` positions
  inside a stage carry flat-sharded (FSDP-tier) stage weights gathered
  in-group just-in-time, the inter-stage hop crosses the node tier
  (priced by :func:`~heat_tpu.telemetry.collectives.pipeline_hop_cost`),
  and a static :class:`~.schedule.ScheduleTable` (gpipe or 1f1b) drives
  one unrolled forward/backward program with a hand-rolled per-microbatch
  vjp: each stage stashes only the INPUT activation of in-flight
  microbatches and rematerializes its forward inside the backward tick
  (`jax.checkpoint` per layer), so the stash is ``stash_depth`` deep —
  ``M`` for gpipe, ``min(S, M)`` for 1f1b.

Within-stage compute is REPLICATED across the ``local`` tier (weights are
sharded ``1/local``, activations are not row-split): the grad of a
microbatch is therefore identical on every group member and each member
slices its own chunk — no gradient collective at all — which is what
makes the elastic contract bit-exact across ``node × local``
re-factorizations (a row-split data tier would change the gradient
reduction order with ``local``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..core import program_cache
from ..core.communication import to_varying
from ..telemetry import collectives as _coll
from . import schedule as _schedule


def stack_stage_params(params_list: Sequence[Any]):
    """Stack per-stage pytrees into one pytree with leading stage dim
    (shard it over the mesh axis with ``comm.sharding(0, leaf.ndim)``)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params_list)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    comm,
    n_microbatches: int,
) -> jax.Array:
    """Apply ``stage_{p-1} ∘ … ∘ stage_0`` to ``x`` with the GPipe schedule.

    ``stage_fn(params, h) -> h`` must preserve the activation shape (the
    classic homogeneous-pipeline contract) and contain no collectives (its
    compute is guarded by a per-position ``lax.cond``). ``stacked_params``
    leaves carry a leading dim of size ``comm.size`` (stage-major, sharded
    or replicated — the kernel slices its own stage either way). ``x`` is
    the full batch ``(B, ...)``, ``B`` divisible by ``n_microbatches``;
    the result is replicated (every position holds the full output after
    the final psum).

    The program is memoized at site ``pipeline.apply`` keyed on the stage
    fn's identity and the microbatch count — repeat calls (any shapes:
    aval dispatch happens inside the cached wrapper) are pure cache hits,
    zero retraces (the CompileWatcher oracle in ``tests/test_pipeline.py``).
    """
    p = comm.size
    axis = comm.axis_name
    m = int(n_microbatches)
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    bad = [
        l.shape[:1]
        for l in jax.tree_util.tree_leaves(stacked_params)
        if l.shape[:1] != (p,)
    ]
    if bad:
        # a 2p stack would silently shard 2 stages per position and run
        # only the first of each — reject any mismatched leaf loudly
        raise ValueError(
            f"stacked_params leaves carry leading dims {sorted(set(bad))} for "
            f"a {p}-position mesh; exactly one stage per position is required"
        )
    mb = b // m
    micro = x.reshape(m, mb, *x.shape[1:])
    fwd_perm = [(i, (i + 1) % p) for i in range(p)]

    def build():
        from jax.sharding import PartitionSpec as P

        def kernel(params_blk, micro_all):
            # params_blk leaves: (1, ...) when sharded — this position's stage
            params = jax.tree_util.tree_map(lambda l: l[0], params_blk)
            s = comm.axis_index()
            act = jnp.zeros(micro_all.shape[1:], micro_all.dtype)
            out = jnp.zeros_like(micro_all)
            # fresh accumulators are replicated; the loop carry mixes with
            # device-varying values (same pcast pattern as ring_attention)
            act, out = (
                jax.lax.pcast(a, (axis,), to="varying") for a in (act, out)
            )

            def tick(t, carry):
                act, out = carry
                # stage 0 injects microbatch t (if any remain)
                inject = jax.lax.dynamic_index_in_dim(
                    micro_all, jnp.minimum(t, m - 1), keepdims=False
                )
                inject = jax.lax.pcast(inject, (axis,), to="varying")
                act = jnp.where((s == 0) & (t < m), inject, act)
                mth = t - s  # microbatch index flowing through this stage now
                active = (mth >= 0) & (mth < m)
                # inactive warmup/drain positions skip the stage compute
                # entirely (the ISSUE 19 dead-compute fix: cond, not
                # compute-and-discard through jnp.where)
                h = jax.lax.cond(
                    active,
                    lambda a: stage_fn(params, a),
                    lambda a: a,
                    act,
                )
                # last stage collects its finished microbatch
                out = jax.lax.cond(
                    (s == p - 1) & active,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, h, jnp.maximum(mth, 0), axis=0
                    ),
                    lambda o: o,
                    out,
                )
                # stage->stage hop through the wrapper chokepoint (ISSUE 15:
                # priced by pipeline_cost, visible to the HLO auditor); exact
                # pinned — activations are the model's forward values
                act = comm.ppermute(h, fwd_perm, precision="off")
                return act, out

            act, out = jax.lax.fori_loop(0, p + m - 1, tick, (act, out))
            # only the last position ever wrote `out` (others carry their zero
            # init), so the psum both collects and replicates the result —
            # exact by construction (one nonzero contribution per element)
            return comm.psum(out, precision="off")

        def apply_fn(stacked, micro_all):
            pspec = jax.tree_util.tree_map(
                lambda l: comm.spec(0, l.ndim), stacked
            )
            return jax.shard_map(
                kernel,
                mesh=comm.mesh,
                in_specs=(pspec, P()),
                out_specs=P(),
            )(stacked, micro_all)

        return apply_fn

    prog = program_cache.cached_program(
        "pipeline.apply", (stage_fn, m), build, comm=comm
    )
    out = prog(stacked_params, micro)
    return out.reshape(b, *x.shape[1:])


# -- the schedule-table MPMD kernel (site pipeline.step) ----------------------


@dataclass(frozen=True)
class PipelineLayout:
    """The chunked stage-layer parameter layout behind ``ht.nn.Pipeline``.

    ``n_layers`` homogeneous layers (identical param pytrees) are grouped
    ``lps = n_layers / n_stages`` per stage; each param leaf of logical
    shape ``shape_k`` lives as a ``(p, lps, chunk_k)`` row array sharded
    over the flat axis — position ``(s, l)`` holds, for each of its
    stage's layers, the ``l``-th ``chunk_k = ceil(numel_k / local)`` slice
    of the flattened leaf (zero-padded tail). The layout is
    topology-INDEPENDENT in logical form (per-layer unpadded leaves), so
    checkpoints restore across ``node × local`` re-factorizations."""

    p: int
    n_stages: int
    n_layers: int
    treedef: Any                       # one layer's params treedef
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    wire: str                          # "off" | "bf16"

    @property
    def local(self) -> int:
        return self.p // self.n_stages

    @property
    def layers_per_stage(self) -> int:
        return self.n_layers // self.n_stages

    def numel(self, k: int) -> int:
        n = 1
        for d in self.shapes[k]:
            n *= int(d)
        return n

    def chunk(self, k: int) -> int:
        return -(-self.numel(k) // self.local)

    def row_shapes(self) -> set:
        return {
            (self.p, self.layers_per_stage, self.chunk(k))
            for k in range(len(self.shapes))
        }

    def signature(self) -> tuple:
        return (
            self.p, self.n_stages, self.n_layers, self.treedef,
            self.shapes, self.dtypes, self.wire,
        )

    def bytes_per_device(self) -> int:
        return sum(
            self.layers_per_stage * self.chunk(k)
            * jnp.dtype(self.dtypes[k]).itemsize
            for k in range(len(self.shapes))
        )


def plan_pipeline(
    layer_params: Sequence[Any],
    mapping: _schedule.StageMapping,
    wire: str = "off",
) -> PipelineLayout:
    """Resolve the layout from one logical per-layer params list.

    All layers must be homogeneous (same treedef, leaf shapes and
    dtypes — the classic pipeline contract, which is also what lets a
    checkpoint re-stage onto any divisor stage count). ``wire`` is the
    in-stage gather's wire mode; the layout supports ``off`` (exact) and
    ``bf16`` — the blockwise/int8 modes of the flat FSDP stream would
    make chunk-boundary-dependent quantization decisions, which the
    elastic bit-exact contract forbids, so they coerce to ``bf16``."""
    layers = list(layer_params)
    L = len(layers)
    if L == 0:
        raise ValueError("need at least one layer")
    if L % mapping.n_stages:
        raise ValueError(
            f"{L} layers do not divide into {mapping.n_stages} equal stages"
        )
    leaves0, treedef = jax.tree_util.tree_flatten(layers[0])
    shapes = tuple(tuple(l.shape) for l in leaves0)
    dtypes = tuple(str(jnp.asarray(l).dtype) for l in leaves0)
    for j, layer in enumerate(layers[1:], start=1):
        lj, tj = jax.tree_util.tree_flatten(layer)
        if tj != treedef or tuple(tuple(l.shape) for l in lj) != shapes:
            raise ValueError(
                f"layer {j} is not homogeneous with layer 0 "
                "(pipeline stages must share one parameter signature)"
            )
    if wire in ("int8", "blockwise"):
        wire = "bf16"
    if wire not in ("off", "bf16"):
        raise ValueError(f"unsupported pipeline gather wire {wire!r}")
    return PipelineLayout(
        mapping.p, mapping.n_stages, L, treedef, shapes, dtypes, wire
    )


def shard_pipeline_params(layer_params: Sequence[Any], layout, comm):
    """Logical per-layer list → the persistent ``(p, lps, chunk)`` rows."""
    layers = list(layer_params)
    lps, loc, S = layout.layers_per_stage, layout.local, layout.n_stages
    by_layer = [jax.tree_util.tree_flatten(l)[0] for l in layers]
    out = []
    for k in range(len(layout.shapes)):
        chunk = layout.chunk(k)
        flat = jnp.stack(
            [
                jnp.pad(
                    jnp.asarray(by_layer[j][k]).reshape(-1),
                    (0, loc * chunk - layout.numel(k)),
                )
                for j in range(layout.n_layers)
            ]
        )  # (L, local*chunk)
        rows = (
            flat.reshape(S, lps, loc, chunk)
            .transpose(0, 2, 1, 3)
            .reshape(layout.p, lps, chunk)
        )
        out.append(jax.device_put(rows, comm.sharding(0, 3)))
    return jax.tree_util.tree_unflatten(layout.treedef, out)


def unshard_pipeline_params(stacked, layout) -> List[Any]:
    """Persistent rows → logical per-layer numpy list (checkpoint form)."""
    import numpy as np

    leaves = jax.tree_util.tree_flatten(stacked)[0]
    lps, loc, S = layout.layers_per_stage, layout.local, layout.n_stages
    per_layer_leaves: List[List[Any]] = [[] for _ in range(layout.n_layers)]
    for k, rows in enumerate(leaves):
        chunk = layout.chunk(k)
        flat = (
            np.asarray(rows)
            .reshape(S, loc, lps, chunk)
            .transpose(0, 2, 1, 3)
            .reshape(layout.n_layers, loc * chunk)
        )
        for j in range(layout.n_layers):
            per_layer_leaves[j].append(
                flat[j, : layout.numel(k)].reshape(layout.shapes[k])
            )
    return [
        jax.tree_util.tree_unflatten(layout.treedef, ls)
        for ls in per_layer_leaves
    ]


def unshard_state_rows(rows, layout, numel: int, shape) -> Any:
    """One ``(p, lps, chunk)`` optimizer-state leaf → stacked logical
    ``(n_layers, *shape)`` (the per-param-leaf correspondence supplies
    ``numel``/``shape`` — row shapes alone cannot, two leaves may share a
    chunk size)."""
    import numpy as np

    lps, loc, S = layout.layers_per_stage, layout.local, layout.n_stages
    chunk = rows.shape[-1]
    flat = (
        np.asarray(rows)
        .reshape(S, loc, lps, chunk)
        .transpose(0, 2, 1, 3)
        .reshape(layout.n_layers, loc * chunk)
    )
    return flat[:, :numel].reshape((layout.n_layers,) + tuple(shape))


def shard_state_rows(logical, layout, comm):
    """Stacked logical ``(n_layers, *shape)`` → ``(p, lps, chunk)`` rows."""
    logical = jnp.asarray(logical)
    L = layout.n_layers
    lps, loc, S = layout.layers_per_stage, layout.local, layout.n_stages
    numel = 1
    for d in logical.shape[1:]:
        numel *= int(d)
    chunk = -(-numel // loc)
    flat = jnp.pad(
        logical.reshape(L, numel), ((0, 0), (0, loc * chunk - numel))
    )
    rows = (
        flat.reshape(S, lps, loc, chunk)
        .transpose(0, 2, 1, 3)
        .reshape(layout.p, lps, chunk)
    )
    return jax.device_put(rows, comm.sharding(0, 3))


def _tie(x, token):
    """Schedule barrier: value-identity, but XLA cannot issue any op
    consuming ``x`` before ``token`` exists — the gather-prefetch window
    bound (no custom vjp needed here: the pipeline kernel's backward is
    hand-rolled per tick, nothing differentiates through the tie)."""
    if token is None:
        return x
    out, _ = jax.lax.optimization_barrier((x, token))
    return out


def _gather_chunk(chunk_val, axis, mapping, wire):
    """In-stage grouped all-gather of one layer-leaf chunk: ``(chunk,)`` →
    ``(local, chunk)`` over this position's stage group (the node-group
    ICI tier — zero DCN bytes). ``bf16`` moves a 2-byte wire element."""
    if mapping.local == 1:
        return chunk_val[None]
    groups = mapping.groups()
    payload = chunk_val
    lossy = wire == "bf16" and jnp.issubdtype(chunk_val.dtype, jnp.floating)
    if lossy:
        payload = payload.astype(jnp.bfloat16)
    telemetry.trace_event(
        "pipeline_gather",
        axis=axis,
        wire="bf16" if lossy else "off",
        collective="all-gather",
        bytes=mapping.p * (mapping.local - 1) * int(chunk_val.shape[0])
        * (2 if lossy else chunk_val.dtype.itemsize),
        group=mapping.describe(),
    )
    full = jax.lax.all_gather(  # heatlint: disable=HL002 -- in-stage
        # GROUPED gather (axis_index_groups = the stage members): the comm
        # wrapper has no grouped form; the pipeline_gather event above is
        # its telemetry/pricing chokepoint, mirroring core/topology.py
        payload, axis, axis_index_groups=groups, tiled=False
    )
    if lossy:
        full = full.astype(chunk_val.dtype)
    return full


def _chunk_slice(full, member, local, chunk):
    """This member's ``(chunk,)`` slice of one full gradient leaf
    (zero-padded tail) — the no-wire ZeRO slice of a replicated grad."""
    flat = full.reshape(-1)
    pad = local * chunk - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return jax.lax.dynamic_slice(flat, (member * chunk,), (chunk,))


def pipeline_step_program(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    layout: PipelineLayout,
    mapping: _schedule.StageMapping,
    table: _schedule.ScheduleTable,
    *,
    comm,
    loss_fn: Optional[Callable] = None,
    optimizer=None,
    prefetch: int = 0,
    remat: bool = True,
) -> Callable:
    """The cached schedule-table pipeline program (site ``pipeline.step``).

    Training tables (``table.train`` with ``loss_fn``/``optimizer``)
    return ``step(params, opt_state, micro_x, micro_y) -> (params,
    opt_state, loss)``; forward tables return ``fwd(params, micro_x) ->
    (M, mb, ...)``. ``micro_*`` carry the microbatch-major
    ``(M, mb, ...)`` reshape of the replicated batch.

    One unrolled program: per static tick, each position looks its stage's
    action up in the baked table, `lax.cond`-guards the forward (gather →
    layer chain, input stashed) and backward (gather → per-microbatch
    ``jax.vjp`` with per-layer `jax.checkpoint` remat, grad chunk-sliced,
    accumulated), then both inter-stage hops permute unconditionally —
    the uniform-collective SPMD contract: gathers sit inside conds whose
    predicate is uniform across each stage group, permutes outside any
    cond. Gradients accumulate in increasing microbatch order on every
    stage for BOTH schedules, which is the cross-schedule bit-identity
    invariant the CI gate pins."""
    train = table.train
    if train and (loss_fn is None or optimizer is None):
        raise ValueError("training tables need loss_fn and optimizer")
    axis = comm.axis_name
    p, S, M = layout.p, mapping.n_stages, table.n_microbatches
    loc, lps = mapping.local, layout.layers_per_stage
    K = table.stash_depth()
    fwd_tab, bwd_tab = table.action_arrays()
    fwd_perm, bwd_perm = mapping.fwd_perm(), mapping.bwd_perm()
    n_leaves = len(layout.shapes)
    depth = int(prefetch)

    def local_leaves(params_blk):
        # (1, lps, chunk) blocks -> this position's (lps, chunk) leaves
        return [
            l[0] for l in jax.tree_util.tree_flatten(params_blk)[0]
        ]

    def gather_layer(pleaves, j, tie_token):
        ws = []
        for k in range(n_leaves):
            chunk_val = _tie(pleaves[k][j], tie_token)
            full = _gather_chunk(chunk_val, axis, mapping, layout.wire)
            ws.append(
                full.reshape(-1)[: layout.numel(k)].reshape(layout.shapes[k])
            )
        return jax.tree_util.tree_unflatten(layout.treedef, ws)

    def stage_forward(pleaves, x0):
        # fwd-tick chain: gather each layer just-in-time, prefetch window
        # `depth` tied to the activation `depth` layers back
        acts = [x0]
        h = x0
        for j in range(lps):
            w = gather_layer(pleaves, j, acts[max(0, j - depth)])
            h = layer_fn(w, h)
            acts.append(h)
        return h

    layer_apply = jax.checkpoint(layer_fn) if remat else layer_fn

    def apply_gathered(ws, x0):
        # bwd-tick recompute target: weights pre-gathered OUTSIDE the vjp
        # (no collective differentiates; the replicated-compute grad needs
        # a plain slice, not an all-gather transpose)
        h = x0
        for w in ws:
            h = layer_apply(w, h)
        return h

    hop_cost = None
    leaf0_item = jnp.dtype(layout.dtypes[0]).itemsize

    def emit_tick_events(t, mb_numel):
        nonlocal hop_cost
        frow, brow = fwd_tab[t], bwd_tab[t]
        busy = sum(1 for s in range(S) if frow[s] >= 0 or brow[s] >= 0)
        from ..core import topology as _topo

        active = _topo.active(p)
        hop_cost = _coll.pipeline_hop_cost(
            1, mb_numel, leaf0_item, p, stride=loc,
            local=active.local if active is not None else None,
        )
        telemetry.trace_event(
            "pipeline_tick",
            tick=t,
            schedule=table.name,
            phase=table.phase_of(t),
            stages=S,
            n_fwd=sum(1 for v in frow if v >= 0),
            n_bwd=sum(1 for v in brow if v >= 0),
            bubble=S - busy,
            hops=(2 if train else 1) if t < table.n_ticks - 1 else 0,
            **{f"hop_{k}": v for k, v in hop_cost.as_fields().items()},
        )

    def build():
        from jax.sharding import PartitionSpec as P

        def kernel(sflags, params_blk, opt_blk, micro_x, micro_y):
            i = jax.lax.axis_index(axis)
            sI, mI = i // loc, i % loc
            pleaves = local_leaves(params_blk)
            mb_shape = micro_x.shape[1:]
            mb_numel = 1
            for d in mb_shape[1:]:
                mb_numel *= int(d)
            varying = lambda v: to_varying(v, axis)
            micro_x = varying(micro_x)
            if train:
                micro_y = varying(micro_y)
            fwd_in = varying(jnp.zeros(mb_shape, micro_x.dtype))
            bwd_in = varying(jnp.zeros(mb_shape, micro_x.dtype))
            stash = varying(jnp.zeros((K,) + mb_shape, micro_x.dtype))
            loss_acc = varying(jnp.zeros((), jnp.float32))
            out = varying(jnp.zeros_like(micro_x)) if not train else None
            grad_acc = [
                varying(jnp.zeros_like(l)) for l in pleaves
            ] if train else None

            for t in range(table.n_ticks):
                emit_tick_events(t, int(mb_shape[0]) * mb_numel)
                frow = jnp.asarray(fwd_tab[t], jnp.int32)
                brow = jnp.asarray(bwd_tab[t], jnp.int32)
                my_f = jnp.take(frow, sI)
                my_b = jnp.take(brow, sI)
                do_f, do_b = my_f >= 0, my_b >= 0

                inject = jax.lax.dynamic_index_in_dim(
                    micro_x, jnp.clip(my_f, 0, M - 1), keepdims=False
                )
                h_in = jnp.where(sI == 0, inject, fwd_in)

                def fwd_branch(stash, h_in, my_f):
                    new_stash = jax.lax.dynamic_update_index_in_dim(
                        stash, h_in, jnp.remainder(my_f, K), axis=0
                    )
                    return new_stash, stage_forward(pleaves, h_in)

                stash, h_out = jax.lax.cond(
                    do_f,
                    fwd_branch,
                    lambda stash, h_in, my_f: (stash, h_in),
                    stash, h_in, my_f,
                )

                if not train:
                    out = jax.lax.cond(
                        (sI == S - 1) & (mI == 0) & do_f,
                        lambda o, h, m: jax.lax.dynamic_update_index_in_dim(
                            o, h, jnp.clip(m, 0, M - 1), axis=0
                        ),
                        lambda o, h, m: o,
                        out, h_out, my_f,
                    )
                else:
                    def bwd_branch(stash, bwd_in, my_b, loss_acc, *gacc):
                        x_in = jax.lax.dynamic_index_in_dim(
                            stash, jnp.remainder(my_b, K), keepdims=False
                        )
                        ws = [
                            gather_layer(pleaves, j, None)
                            for j in range(lps)
                        ]
                        y_mb = jax.lax.dynamic_index_in_dim(
                            micro_y, jnp.clip(my_b, 0, M - 1), keepdims=False
                        )

                        def last(ws, x_in, g_in):
                            def fl(ws, xi):
                                return (
                                    loss_fn(apply_gathered(ws, xi), y_mb) / M
                                )

                            lval, vjp = jax.vjp(fl, ws, x_in)
                            dws, dx = vjp(varying(jnp.ones((), lval.dtype)))
                            return dws, dx, lval.astype(jnp.float32)

                        def mid(ws, x_in, g_in):
                            _, vjp = jax.vjp(apply_gathered, ws, x_in)
                            dws, dx = vjp(g_in)
                            return dws, dx, varying(
                                jnp.zeros((), jnp.float32)
                            )

                        dws, dx, lval = jax.lax.cond(
                            sI == S - 1, last, mid, ws, x_in, bwd_in
                        )
                        dleaves = [
                            jax.tree_util.tree_flatten(dw)[0] for dw in dws
                        ]
                        new_gacc = []
                        for k in range(n_leaves):
                            upd = jnp.stack(
                                [
                                    _chunk_slice(
                                        dleaves[j][k], mI, loc,
                                        layout.chunk(k),
                                    )
                                    for j in range(lps)
                                ]
                            )
                            new_gacc.append(
                                gacc[k] + upd.astype(gacc[k].dtype)
                            )
                        return (dx, loss_acc + lval) + tuple(new_gacc)

                    res = jax.lax.cond(
                        do_b,
                        bwd_branch,
                        lambda stash, bwd_in, my_b, loss_acc, *gacc: (
                            (
                                varying(
                                    jnp.zeros(mb_shape, micro_x.dtype)
                                ),
                                loss_acc,
                            )
                            + tuple(gacc)
                        ),
                        stash, bwd_in, my_b, loss_acc, *grad_acc,
                    )
                    dx_out, loss_acc = res[0], res[1]
                    grad_acc = list(res[2:])

                # the inter-stage hops: unconditional (uniform SPMD), one
                # fwd and — training — one bwd collective-permute per tick,
                # each priced by pipeline_hop_cost (DCN when the stage
                # boundary crosses the node tier), audited zero-drift. The
                # final tick ships nothing (no later tick could consume the
                # payload), so the compiled program emits exactly
                # 2 x (n_ticks - 1) permutes and the analytic total agrees.
                if t < table.n_ticks - 1:
                    recv_f = comm.ppermute(h_out, fwd_perm, precision="off")
                    f_sent = (sI > 0) & (
                        jnp.take(frow, jnp.maximum(sI - 1, 0)) >= 0
                    )
                    fwd_in = jnp.where(f_sent, recv_f, fwd_in)
                    if train:
                        recv_b = comm.ppermute(
                            dx_out, bwd_perm, precision="off"
                        )
                        b_sent = (sI < S - 1) & (
                            jnp.take(brow, jnp.minimum(sI + 1, S - 1)) >= 0
                        )
                        bwd_in = jnp.where(b_sent, recv_b, bwd_in)

            if not train:
                return comm.psum(out, precision="off")

            # per-chunk optimizer update (ZeRO-composed: padded grad cells
            # are zero, elementwise transforms keep them zero)
            import optax

            params_local = jax.tree_util.tree_unflatten(
                layout.treedef, pleaves
            )
            grads = jax.tree_util.tree_unflatten(layout.treedef, grad_acc)
            opt_local = jax.tree_util.tree_map(
                lambda l, f: l[0] if f else l, opt_blk, sflags
            )
            updates, opt_new = optimizer.update(
                grads, opt_local, params_local
            )
            params_new = optax.apply_updates(params_local, updates)
            loss = comm.psum(
                jnp.where((sI == S - 1) & (mI == 0), loss_acc, 0.0),
                precision="off",
            )
            return (
                jax.tree_util.tree_map(lambda l: l[None], params_new),
                jax.tree_util.tree_map(
                    lambda l, f: l[None] if f else l, opt_new, sflags
                ),
                loss,
            )

        p_specs = jax.tree_util.tree_unflatten(
            layout.treedef, [P(axis)] * n_leaves
        )
        from ..core import topology as _topo

        # a tiered psum ends in an in-node all-gather, whose replication
        # the varying-axis checker cannot infer for the P() outputs
        check_vma = _topo.active(p) is None

        if train:
            def step(params, opt_state, micro_x, micro_y):
                rows = layout.row_shapes()
                sflags = jax.tree_util.tree_map(
                    lambda l: tuple(getattr(l, "shape", ())) in rows,
                    opt_state,
                )
                s_specs = jax.tree_util.tree_map(
                    lambda f: P(axis) if f else P(), sflags
                )
                return jax.shard_map(
                    lambda *a: kernel(sflags, *a),
                    mesh=comm.mesh,
                    in_specs=(p_specs, s_specs, P(), P()),
                    out_specs=(p_specs, s_specs, P()),
                    check_vma=check_vma,
                )(params, opt_state, micro_x, micro_y)

            return step

        def fwd(params, micro_x):
            return jax.shard_map(
                lambda pp, xx: kernel(None, pp, None, xx, None),
                mesh=comm.mesh,
                in_specs=(p_specs, P()),
                out_specs=P(),
                check_vma=check_vma,
            )(params, micro_x)

        return fwd

    return program_cache.cached_program(
        "pipeline.step",
        (
            layer_fn, loss_fn, optimizer, layout.signature(),
            mapping.describe(), table.name, table.train, S, M,
            depth, remat,
        ),
        build,
        comm=comm,
    )
