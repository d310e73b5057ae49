"""Fully-sharded data parallelism (ISSUE 18) — the :class:`DataParallel`
twin whose parameters live as flat 1/p shards on the mesh.

ZeRO (PR 15) sharded the optimizer *state* but kept every parameter
replicated; :class:`FSDP` closes the gap for the big-model scenario
(ROADMAP item 3): parameters persist in the
:func:`heat_tpu.parallel.fsdp.fsdp_shard` layout across steps, and each
layer's weights are all-gathered just-in-time
(:func:`heat_tpu.parallel.fsdp.fsdp_gather` — tiered under
``HEAT_TPU_HIERARCHICAL=1``, wire-compressed per partition rule),
consumed, and dropped. Layouts come from a regex
:class:`~heat_tpu.parallel.fsdp.PartitionRules` table, so arbitrary
pytrees — not just the nn/ demos — get placements declaratively.

Two memory disciplines bound the transient footprint:

* **Per-stage rematerialization** — each stage's gather sits INSIDE its
  ``jax.checkpoint`` region, so the backward re-gathers weights instead
  of holding every layer's full parameters as residuals (the
  arXiv:2112.01075 bounded-decomposition discipline, applied to the
  weight stream the way PR 6 applied it to relayout).
* **Prefetch windowing** — ``HEAT_TPU_FSDP_PREFETCH`` depth ``d`` issues
  stage ``k``'s gather alongside stage ``k−d``'s compute (the
  communication-scheduling recipe of arXiv:2211.05322): an
  ``optimization_barrier`` ties each gather's chunk inputs to the
  activation produced ``d`` stages earlier, so XLA may hide the gather
  under the GEMMs but can NOT hoist every gather to the top of the
  program — at most ``d+1`` stages' full weights are live at once.
  Depth 0 is fully serial. Pure scheduling: outputs are bit-identical
  at every depth.

``HEAT_TPU_FSDP=0`` (the default) keeps the replicated
:class:`DataParallel` dispatch bit-for-bit — same program family, same
cache site — so the knob is a pure opt-in. ZeRO composes: the optimizer
state follows the sharded parameter layout (sharded state over sharded
params), and checkpoints are written in the topology-independent
*logical* form, so a run restarted on a different mesh factorization
restores bit-exactly (the same property
:class:`~heat_tpu.optim.ZeroOptimizer` pins).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from heat_tpu import _knobs as knobs

from ..core import program_cache
from ..core.communication import MeshCommunication, sanitize_comm
from ..parallel import fsdp as _fsdp
from .data_parallel import DataParallel, _module_apply

__all__ = ["FSDP"]


def _tie(tree: Any, token):
    """Schedule barrier: the returned tree is value-identical to
    ``tree``, but XLA cannot start any op consuming it before ``token``
    (an activation) exists — the prefetch-window bound. Differentiable
    as the identity (``optimization_barrier`` has no built-in rule):
    leaf cotangents pass straight through, and ``token``'s gradient path
    is cut — its real cotangent flows through the stage that actually
    consumes the activation, not through the barrier."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    tok = jax.lax.stop_gradient(token)
    # the token's zero cotangent must carry the primal's varying axes
    tok_vma = tuple(jax.typeof(tok).vma)

    def impl(args):
        out = jax.lax.optimization_barrier(tuple(args))
        return tuple(out[:-1])

    @jax.custom_vjp
    def barrier(*args):
        return impl(args)

    def fwd(*args):
        return impl(args), None

    def bwd(_, ct):
        zero = jnp.zeros(tok.shape, tok.dtype)
        if tok_vma:
            zero = jax.lax.pcast(zero, tok_vma, to="varying")
        return tuple(ct) + (zero,)

    barrier.defvjp(fwd, bwd)
    out = barrier(*(tuple(leaves) + (tok,)))
    return jax.tree_util.tree_unflatten(treedef, list(out))


class FSDP(DataParallel):
    """Fully-sharded data parallelism over the communicator's mesh.

    Parameters
    ----------
    module : flax.linen.Module, callable, or a sequence of them
        A single network, or a SEQUENCE of stages applied left-to-right
        (``x = stage_k(params_k, x)``). The sequential form is the one
        that bounds transient memory and overlaps gathers with compute:
        weights gather per stage, not all at once. A single module
        gathers everything up front — still a persistent-memory win
        (params live 1/p between steps), but no per-layer streaming.
    comm : MeshCommunication, optional
        Mesh whose single axis is the data-parallel axis.
    optimizer : optax.GradientTransformation, optional
        Bound optimizer used by :meth:`make_train_step` /
        :meth:`init_opt_state`.
    rules : heat_tpu.parallel.PartitionRules, optional
        The layout table (default: shard every non-scalar leaf).
    precision : str, optional
        Instance-wide wire override for gathers whose rule pins none
        (``off | bf16 | int8 | blockwise``); default inherits the
        :func:`heat_tpu.core.topology.fsdp_wire` chain.
    prefetch : int, optional
        Gather-prefetch depth; default ``HEAT_TPU_FSDP_PREFETCH``.

    The ``HEAT_TPU_FSDP`` knob and prefetch depth are resolved at
    construction (like ZeroOptimizer's wire mode): the layout is part of
    the training state, not something to flip mid-run.
    """

    def __init__(
        self,
        module,
        comm: Optional[MeshCommunication] = None,
        optimizer=None,
        rules=None,
        precision: Optional[str] = None,
        prefetch: Optional[int] = None,
    ):
        self._multi = isinstance(module, (list, tuple))
        stages = list(module) if self._multi else [module]
        self.stage_apply: List[Callable] = [_module_apply(m) for m in stages]
        self.stages = stages
        multi = self._multi
        stage_apply = self.stage_apply

        def full_apply(params, *inputs):
            x = inputs[0]
            for f, sp in zip(stage_apply, params if multi else [params]):
                x = f(sp, x)
            return x

        super().__init__(
            full_apply, comm, optimizer, blocking_parameter_updates=True
        )
        self.module = module
        self.rules = (
            rules if rules is not None else _fsdp.PartitionRules.fsdp_default()
        )
        self.precision = precision
        self.enabled = bool(knobs.get("HEAT_TPU_FSDP"))
        self.prefetch = int(
            prefetch
            if prefetch is not None
            else knobs.get("HEAT_TPU_FSDP_PREFETCH")
        )
        if self.prefetch < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {self.prefetch}")
        self._plan: Optional[_fsdp.FsdpPlan] = None
        self._loss_wrappers: dict = {}

    # -- initialization / layout ----------------------------------------------

    def init(self, rngs, *sample_inputs):
        """Initialize parameters in the LOGICAL (replicated) form —
        :meth:`shard_params` places them. Sequential mode initializes
        stage by stage, flowing the sample activation forward (stages
        must be flax modules; bare callables cannot self-initialize)."""
        if not self._multi:
            return super().init(rngs, *sample_inputs)
        x = sample_inputs[0]
        params = []
        for i, m in enumerate(self.stages):
            if not hasattr(m, "init"):
                raise TypeError(
                    f"stage {i} is a bare callable — sequential FSDP.init "
                    "needs flax modules; build per-stage params yourself "
                    "and call shard_params instead"
                )
            key = jax.random.fold_in(rngs, i)
            p_i = m.init(key, x)
            x = m.apply(p_i, x)
            params.append(p_i)
        return jax.device_put(tuple(params), self.comm.replicated())

    def plan(self, params) -> _fsdp.FsdpPlan:
        """Resolve (and pin) the partition plan from a logical parameter
        tree. Re-planning with different shapes replaces the pin."""
        self._plan = _fsdp.plan_partition(
            params, self.rules, self.comm, precision=self.precision
        )
        return self._plan

    def _ensure_plan(self, params) -> _fsdp.FsdpPlan:
        if self._plan is None:
            return self.plan(params)
        return self._plan

    def shard_params(self, params):
        """Logical → persistent layout: the plan's flat ``(p, chunk)``
        rows for sharded leaves (knob off: replicated, the DataParallel
        layout — bit-for-bit the baseline)."""
        if not self.enabled:
            return jax.device_put(params, self.comm.replicated())
        return _fsdp.fsdp_shard(params, self._ensure_plan(params), self.comm)

    def unshard_params(self, params):
        """Persistent layout → logical numpy (checkpoint interchange)."""
        import numpy as np

        if not self.enabled:
            return jax.tree_util.tree_map(np.asarray, params)
        if self._plan is None:
            raise ValueError("no plan pinned — call shard_params/plan first")
        return _fsdp.fsdp_unshard(params, self._plan)

    def param_bytes_per_device(self, params) -> int:
        """Worst-case per-device live parameter bytes (the watermark
        figure the CI gate compares against the replicated baseline)."""
        return _fsdp.bytes_per_device(params)

    # -- state layout helpers --------------------------------------------------

    def _param_flags(self, plan):
        return plan.unflatten([l.sharded for l in plan.leaves])

    def _state_template_flags(self, optimizer, params_sharded, plan):
        """Per-state-leaf sharded flags: a state leaf is sharded iff its
        shape is one of the plan's ``(p, chunk)`` row shapes (collisions
        with replicated leaves are rejected at plan time, so the shape
        test is sound)."""
        template = jax.eval_shape(optimizer.init, params_sharded)
        rows = {(plan.p, l.chunk) for l in plan.leaves if l.sharded}
        flags = jax.tree_util.tree_map(
            lambda t: tuple(getattr(t, "shape", ())) in rows, template
        )
        return template, flags

    def init_opt_state(self, params):
        """Optimizer state OVER the persistent layout — ZeRO composed on
        FSDP: state leaves for sharded parameters are themselves
        ``(p, chunk)`` rows pinned sharded (each position updates only
        its chunk); replicated parameters keep replicated state."""
        opt = self.optimizer
        if opt is None:
            raise ValueError("no optimizer bound; pass one at construction")
        if not self.enabled:
            return jax.device_put(opt.init(params), self.comm.replicated())
        comm = self.comm
        plan = self._ensure_plan(params)
        _, sflags = self._state_template_flags(opt, params, plan)

        def build():
            def init_fn(ps):
                state = opt.init(ps)
                return jax.tree_util.tree_map(
                    lambda l, f: jax.lax.with_sharding_constraint(
                        l, comm.sharding(0, 2)
                    )
                    if f
                    else l,
                    state,
                    sflags,
                )

            return init_fn

        return program_cache.cached_program(
            "fsdp_opt_init", (opt, plan.signature()), build, comm=comm
        )(params)

    # -- forward ---------------------------------------------------------------

    def _stage_trees(self, params):
        return list(params) if self._multi else [params]

    def _gather_stage(self, stage_params, stage_idx: int, plan):
        """Gather one stage's sharded leaves back to logical form inside
        the kernel (replicated leaves pass through)."""
        comm = self.comm
        prefix = f"{stage_idx}/" if self._multi else ""
        paths = _fsdp.leaf_paths(stage_params)
        treedef = jax.tree_util.tree_structure(stage_params)
        gathered = [
            _fsdp.fsdp_gather(leaf, plan.by_path[prefix + path], comm)
            for path, leaf in paths
        ]
        return jax.tree_util.tree_unflatten(treedef, gathered)

    def _forward_local(self, params, x, plan, depth: int, remat: bool):
        """The staged forward INSIDE a shard_map kernel: per-stage
        gather (optionally rematerialized) with the prefetch-window
        barrier. Returns the final activation."""
        acts = [x]
        out = x
        for k, st in enumerate(self._stage_trees(params)):
            apply_k = self.stage_apply[k]

            def f(sp, tie, xin, _k=k, _apply=apply_k):
                sp = _tie(sp, tie)
                full = self._gather_stage(sp, _k, plan)
                return _apply(full, xin)

            if remat:
                f = jax.checkpoint(f)
            out = f(st, acts[max(0, k - depth)], out)
            acts.append(out)
        return out

    def __call__(self, params, *inputs):
        """Forward pass. Knob off: the replicated ``dp_forward``
        program. Enabled: the gather-streamed shard_map forward (batch
        axis 0 sharded, output sharded along 0)."""
        if not self.enabled:
            return super().__call__(params, *inputs)
        comm = self.comm
        axis = comm.axis_name
        plan = self._ensure_plan(params)
        depth = self.prefetch
        me = self

        def build():
            p_specs = plan.unflatten(
                [P(axis) if l.sharded else P() for l in plan.leaves]
            )

            def kernel(params, x):
                return me._forward_local(params, x, plan, depth, remat=False)

            def fwd(params, x):
                return jax.shard_map(
                    kernel, mesh=comm.mesh,
                    in_specs=(p_specs, P(axis)), out_specs=P(axis),
                )(params, x)

            return fwd

        compiled = program_cache.cached_program(
            "fsdp_forward", (plan.signature(), depth), build, comm=comm
        )
        return compiled(params, *self.shard_batch(*inputs))

    # -- training --------------------------------------------------------------

    def _full_loss(self, loss_fn):
        """``loss_fn(out, *tail)`` lifted to the DataParallel
        ``loss(params, *batch)`` contract (memoized per loss_fn so the
        replicated fallback's program-cache key stays stable)."""
        cached = self._loss_wrappers.get(loss_fn)
        if cached is None:
            apply_fn = self.apply_fn

            def full_loss(params, *batch):
                return loss_fn(apply_fn(params, batch[0]), *batch[1:])

            self._loss_wrappers[loss_fn] = cached = full_loss
        return cached

    def make_train_step(
        self, loss_fn: Callable, optimizer=None,
        precision: Optional[str] = None,
    ) -> Callable:
        """Build the compiled train step:
        ``step(params, opt_state, *batch) -> (params, opt_state, loss)``.

        ``loss_fn(out, *batch_tail) -> scalar`` is the MEAN loss over
        the local batch rows (note the contract differs from
        :class:`DataParallel`, whose loss closes over the forward — FSDP
        must own the forward to schedule the per-stage gathers).

        Knob off (``HEAT_TPU_FSDP=0``): delegates to the replicated
        :class:`DataParallel` blocking step, bit-for-bit. Enabled: one
        shard_map program — staged forward (remat per stage, prefetch
        window ``d``), backward re-gathers and reduce-scatters each
        leaf's gradient chunk via the
        :func:`~heat_tpu.parallel.fsdp.fsdp_gather` custom vjp,
        per-chunk optimizer update (ZeRO-composed state), parameters
        stay sharded. Zero steady-state compiles: the program is
        memoized on (loss, optimizer, plan signature, depth)."""
        optimizer = optimizer if optimizer is not None else self.optimizer
        if optimizer is None:
            raise ValueError("no optimizer bound; pass one here or at init")
        if not self.enabled:
            return super().make_train_step(
                self._full_loss(loss_fn), optimizer, precision=precision
            )
        if self._plan is None:
            raise ValueError(
                "no plan pinned — call shard_params(params) before "
                "make_train_step so the step is traced against the layout"
            )
        from ..core import collective_prec

        comm = self.comm
        axis = comm.axis_name
        p = comm.size
        plan = self._plan
        depth = self.prefetch
        block = collective_prec.block_size()
        me = self

        def build():
            pflags = me._param_flags(plan)
            p_specs = plan.unflatten(
                [P(axis) if l.sharded else P() for l in plan.leaves]
            )

            def local_view(tree, flags):
                return jax.tree_util.tree_map(
                    lambda x, f: x[0] if f else x, tree, flags
                )

            def restack(tree, flags):
                return jax.tree_util.tree_map(
                    lambda x, f: x[None] if f else x, tree, flags
                )

            def kernel(sflags, params, opt_state, *batch):
                x, rest = batch[0], tuple(batch[1:])

                def fwd_loss(ps):
                    out = me._forward_local(ps, x, plan, depth, remat=True)
                    return loss_fn(out, *rest)

                loss, grads = jax.value_and_grad(fwd_loss)(params)
                loss = comm.psum(loss, precision="off") / p

                # sharded leaves: the custom-vjp reduce-scatter already
                # holds this position's chunk of the global SUM; the
                # mean over p local-mean losses divides by p. Replicated
                # leaves sum exactly (their gradients never ride the
                # compressed weight wire).
                def grad_mean(g, f):
                    if f:
                        return g / p
                    return comm.psum(g, precision="off") / p

                grads = jax.tree_util.tree_map(grad_mean, grads, pflags)
                my_p = local_view(params, pflags)
                my_g = local_view(grads, pflags)
                my_s = local_view(opt_state, sflags)
                updates, s_new = optimizer.update(my_g, my_s, my_p)
                p_new = optax.apply_updates(my_p, updates)
                return (
                    restack(p_new, pflags),
                    restack(s_new, sflags),
                    loss,
                )

            def step(params, opt_state, *batch):
                _, sflags = me._state_template_flags(
                    optimizer, params, plan
                )
                s_specs = jax.tree_util.tree_map(
                    lambda f: P(axis) if f else P(), sflags
                )
                in_specs = (p_specs, s_specs) + (P(axis),) * len(batch)
                return jax.shard_map(
                    lambda *a: kernel(sflags, *a),
                    mesh=comm.mesh,
                    in_specs=in_specs,
                    out_specs=(p_specs, s_specs, P()),
                )(params, opt_state, *batch)

            return step

        compiled = program_cache.cached_program(
            "fsdp_train_step",
            (loss_fn, optimizer, plan.signature(), depth, block),
            build,
            comm=comm,
        )
        self._train_step = compiled
        return compiled

    # -- checkpoint / restore --------------------------------------------------

    def _zero(self, optimizer=None):
        """The composed ZeRO view of this instance's optimizer — its
        logical-state machinery is layout-compatible (sharded state
        leaves are ``(p, chunk)`` rows here too)."""
        from ..optim import ZeroOptimizer

        opt = optimizer if optimizer is not None else self.optimizer
        if opt is None:
            raise ValueError("no optimizer bound; pass one at construction")
        return ZeroOptimizer(opt, self.comm, precision="off")

    def save_checkpoint(self, path: str, params, opt_state) -> str:
        """Checkpoint in the topology-independent LOGICAL form (per-leaf
        blobs, CRC-checked, atomic swap): sharded params unshard, sharded
        state rows unpad — the blobs carry no trace of this mesh's size
        or factorization, so restore works across factorizations."""
        from .. import resilience

        logical_p = self.unshard_params(params)
        logical_s = self._zero()._logical_state(logical_p, opt_state)
        return resilience.save_checkpoint(
            {"params": logical_p, "opt_state": logical_s}, path,
            extra={
                "algo": "fsdp",
                "enabled": bool(self.enabled),
                "prefetch": int(self.prefetch),
                "rules": repr(self.rules),
            },
        )

    def load_checkpoint(self, path: str, params_template):
        """Restore onto THIS instance's mesh/plan: logical blobs re-pad
        and re-shard for the current factorization, bit-exactly.
        ``params_template`` supplies structure and logical shapes (e.g.
        a fresh ``init``). Returns ``(params, opt_state)`` in the
        persistent layout."""
        from .. import resilience

        opt = self.optimizer
        if opt is None:
            raise ValueError("no optimizer bound; pass one at construction")
        template_state = jax.eval_shape(opt.init, params_template)
        tree, extra = resilience.load_checkpoint(
            path,
            like={"params": params_template, "opt_state": template_state},
            with_extra=True,
        )
        if extra.get("algo") != "fsdp":
            raise resilience.CheckpointError(
                f"{path!r} is a {extra.get('algo')!r} checkpoint, not fsdp"
            )
        params = self.shard_params(
            jax.tree_util.tree_map(jnp.asarray, tree["params"])
        )
        if not self.enabled:
            return params, jax.device_put(
                jax.tree_util.tree_map(jnp.asarray, tree["opt_state"]),
                self.comm.replicated(),
            )
        plan = self._plan
        template, sflags = self._state_template_flags(opt, params, plan)
        comm = self.comm

        def reshard(l, t, f):
            l = jnp.asarray(l)
            if not f:
                return jax.device_put(l, comm.replicated())
            # the sharded-layout state template carries the exact
            # (p, chunk) row shape this logical leaf re-pads into
            pp, c = t.shape
            flat = l.reshape(-1)
            if pp * c != l.size:
                flat = jnp.pad(flat, (0, pp * c - l.size))
            return jax.device_put(flat.reshape(pp, c), comm.sharding(0, 2))

        opt_state = jax.tree_util.tree_map(
            reshard, tree["opt_state"], template, sflags
        )
        return params, opt_state
