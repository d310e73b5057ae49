"""Data-parallel model wrappers (reference: heat/nn/data_parallel.py).

The reference's :class:`DataParallel` registers per-parameter backward hooks
that Allreduce each gradient — blocking (reference data_parallel.py:223-241)
or overlapped via Iallreduce + next-iteration forward pre-hooks (:243-297).
On TPU the whole train step is one compiled XLA program: sharding the batch
over the mesh makes the gradient mean a `psum` the compiler schedules, and
XLA's latency-hiding scheduler overlaps it with remaining backward compute —
the nonblocking hook machinery exists *inside the compiler*. What this class
provides is the same contract (wrap a model, get synchronous DP semantics)
plus the compiled train-step factory.

:class:`DataParallelMultiGPU` is the hierarchical flavor that pairs with
:class:`heat_tpu.optim.DASO` (reference data_parallel.py:314-376 wraps
node-local torch DDP; here it binds the model to DASO's 2-level mesh).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from .. import telemetry
from ..core import program_cache
from ..core.communication import MeshCommunication, sanitize_comm
from ..core.dndarray import DNDarray

__all__ = ["DataParallel", "DataParallelMultiGPU"]


def _module_apply(module) -> Callable:
    """Accept a flax.linen Module (has .apply) or a bare callable
    ``fn(params, *args)``."""
    if hasattr(module, "apply"):
        return lambda params, *a, **kw: module.apply(params, *a, **kw)
    if callable(module):
        return module
    raise TypeError(
        f"module must be a flax Module or callable(params, *inputs), got {type(module)}"
    )


class DataParallel:
    """Synchronous data parallelism over the communicator's device mesh.

    Parameters
    ----------
    module : flax.linen.Module or callable
        The network; a callable must have signature ``fn(params, *inputs)``.
    comm : MeshCommunication, optional
        Mesh whose single axis is the data-parallel axis.
    optimizer : optax.GradientTransformation, optional
        Bound optimizer used by :meth:`make_train_step`.
    blocking_parameter_updates : bool
        ``True`` (the reference's blocking mode, data_parallel.py:223-241):
        each step applies its own globally-averaged gradients — the psum is
        on the step's critical path.
        ``False`` (the reference's non-blocking mode, :243-297): **explicit
        double buffering** — step ``k`` outputs its averaged gradients and
        applies step ``k−1``'s. Inside the compiled step the psum result is
        only a program *output*, so XLA's latency-hiding scheduler overlaps
        it with the optimizer compute; across steps the average is ready
        before its first consumer. The first step applies zeros, exactly
        like the reference's hooks returning zeros on iteration 0 (:276).
    """

    def __init__(
        self,
        module,
        comm: Optional[MeshCommunication] = None,
        optimizer=None,
        blocking_parameter_updates: bool = False,
    ):
        self.module = module
        self.apply_fn = _module_apply(module)
        self.comm = sanitize_comm(comm)
        self.optimizer = optimizer
        self.blocking_parameter_updates = blocking_parameter_updates
        self._compiled_call = None
        self._train_step = None

    # -- forward -------------------------------------------------------------

    def init(self, rngs, *sample_inputs):
        """Initialize parameters (replicated across the mesh)."""
        params = self.module.init(rngs, *sample_inputs)
        return jax.device_put(params, self.comm.replicated())

    def shard_batch(self, *arrays):
        """Place host arrays batch-sharded (axis 0) over the dp mesh.

        DNDarrays pass through as their device buffer only when already
        split along 0 and evenly sharded — a tail-padded batch would feed
        garbage pad rows into the loss mean (the reference's Dataset slices
        uneven tails off up front, reference datatools.py:147-155; use the
        DataLoader or a divisible batch size)."""
        out = []
        for a in arrays:
            if isinstance(a, DNDarray):
                if a.split not in (None, 0):
                    raise ValueError(
                        f"DataParallel batches must be split along 0, got {a.split}"
                    )
                if a.split == 0 and a.pad_count:
                    raise ValueError(
                        f"batch axis ({a.shape[0]}) must divide evenly over "
                        f"the {self.comm.size}-device mesh; pad rows would "
                        "bias the loss. Use heat_tpu.utils.data.DataLoader "
                        "or a divisible batch size."
                    )
                out.append(a._logical() if a.split is None else a._masked(0))
            else:
                a = jnp.asarray(a)
                out.append(jax.device_put(a, self.comm.sharding(0, a.ndim)))
        return tuple(out)

    def __call__(self, params, *inputs):
        """Forward pass; inputs are batch-sharded, output comes back sharded
        along axis 0 (one compiled program, memoized in the process-global
        program registry — two wrappers over the same module share it)."""
        if self._compiled_call is None:
            self._compiled_call = program_cache.cached_program(
                "dp_forward", self.apply_fn, lambda: self.apply_fn,
                comm=self.comm,
            )
        return self._compiled_call(params, *self.shard_batch(*inputs))

    # -- training ------------------------------------------------------------

    def make_train_step(
        self, loss_fn: Callable, optimizer=None,
        precision: Optional[str] = None, has_aux: bool = False,
        state_rule: Optional[Callable] = None,
    ) -> Callable:
        """Build the compiled DP train step.

        ``loss_fn(params, *batch) -> scalar`` closes over :attr:`apply_fn`.
        With ``has_aux=True`` it returns ``(scalar, aux)`` and the step
        returns ``aux`` after the loss. Either way everything the step
        returns is on the device and the call does not wait for it.

        The step **donates** ``params`` and ``opt_state`` (and
        ``pending_grads``): the arrays passed in are consumed, the returned
        ones take their memory. Keep a copy of what must outlive the call.
        Batch arrays still on the host are placed by :meth:`shard_batch`
        inside the call. Spans: ``heat_tpu.train.step`` with ``.prepare``
        and ``.launch`` (``nn.moe.read_routing`` records ``.readback``); while
        they record, ``telemetry.hlo.program_scopes("dp_train_step")`` gives the
        compiled step's scope map (instruction -> modules, scopes, pass).
        With the batch axis sharded and params replicated, XLA emits exactly
        one gradient psum per step (the reference's per-parameter Allreduce
        hooks, fused). Call with batch arrays sharded via
        :meth:`shard_batch`.

        Blocking mode returns ``step(params, opt_state, *batch) ->
        (params, opt_state, loss)``.

        ``state_rule(state, aux) -> state`` (blocking mode, ``has_aux``): the
        step also carries state that a rule of its own updates, in the same
        compiled program. ``params`` is then a dict of collections, as
        ``module.init`` gives it: ``params["params"]`` is what the loss is
        differentiated by and all the optimizer ever sees (its state is
        ``optimizer.init({"params": params["params"]})``: no moment, no decay
        and no share of a clip's norm for anything else); the other
        collections are ``state``, handed to the loss with the parameters, then
        to the rule with the step's ``aux``, and returned (and donated) inside
        ``params``. ``nn.balance_bias_rule`` is such a rule.

        Non-blocking (double-buffered) mode returns ``step(params,
        opt_state, pending_grads, *batch) -> (params, opt_state,
        next_pending_grads, loss)`` — thread ``pending_grads`` through the
        loop, seeded by :meth:`init_pending`. Step ``k`` applies step
        ``k−1``'s global average while its own psum overlaps the optimizer
        compute (reference data_parallel.py:243-297 semantics: global grads
        applied just-in-time one iteration later).

        ``precision`` (ISSUE 9, default: the global
        ``HEAT_TPU_COLLECTIVE_PREC`` knob): compress the gradient
        all-reduce's wire payload. ``off`` keeps the exact GSPMD step
        bit-for-bit. Compressed modes restructure the step as a
        ``shard_map`` over the dp mesh — each device takes
        ``value_and_grad`` of the loss on its local batch shard and the
        per-leaf gradient *mean* rides a compressed collective
        (cast-psum-upcast for ``bf16``; the EQuARX two-phase quantized
        all-reduce for ``int8``/``blockwise`` — collective_prec.psum).
        This assumes the standard DP contract the reference's DDP hooks
        assume too: ``loss_fn`` is a MEAN over batch rows, so the global
        gradient is the mean of per-shard gradients. The wire mode is
        part of the program signature (modes key separate cache
        entries)."""
        from ..core import collective_prec

        optimizer = optimizer if optimizer is not None else self.optimizer
        if optimizer is None:
            raise ValueError("no optimizer bound; pass one here or at init")
        wire = collective_prec.resolve(precision)

        if wire != "off":
            if has_aux or state_rule is not None:
                raise NotImplementedError("has_aux or state_rule with a compressed gradient wire")
            step = self._make_compressed_step(loss_fn, optimizer, wire)
        elif self.blocking_parameter_updates and state_rule is not None:
            if not has_aux:
                raise ValueError("state_rule reads the loss's aux: pass has_aux=True")

            def step(params, opt_state, *batch):
                trained = {"params": params["params"]}
                state = {k: v for k, v in params.items() if k != "params"}
                out, grads = jax.value_and_grad(
                    lambda p: loss_fn({**state, **p}, *batch), has_aux=True
                )(trained)
                with jax.named_scope("train.optimizer"):
                    updates, opt_state = optimizer.update(grads, opt_state, trained)
                    trained = optax.apply_updates(trained, updates)
                with jax.named_scope("train.state_rule"):
                    state = state_rule(state, out[1])
                return ({**state, **trained}, opt_state, *out)

        elif state_rule is not None:
            raise NotImplementedError("state_rule with double-buffered parameter updates")
        elif self.blocking_parameter_updates:

            def step(params, opt_state, *batch):
                out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params, *batch)
                with jax.named_scope("train.optimizer"):
                    updates, opt_state = optimizer.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return (params, opt_state, *out) if has_aux else (params, opt_state, out)

        else:

            def step(params, opt_state, pending_grads, *batch):
                # trace-time guard: the 3rd argument must be a gradient
                # pytree, catching callers using the blocking-mode arity
                if jax.tree_util.tree_structure(
                    pending_grads
                ) != jax.tree_util.tree_structure(params):
                    raise TypeError(
                        "non-blocking (double-buffered) DataParallel step "
                        "signature is step(params, opt_state, pending_grads, "
                        "*batch) -> (params, opt_state, next_pending, loss); "
                        "seed pending_grads with DataParallel.init_pending("
                        "params), or construct with "
                        "blocking_parameter_updates=True for the 3-tuple step"
                    )
                out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params, *batch)
                # apply the PREVIOUS step's averaged grads; this step's psum
                # only feeds the program output — off the critical path
                with jax.named_scope("train.optimizer"):
                    updates, opt_state = optimizer.update(
                        pending_grads, opt_state, params
                    )
                    params = optax.apply_updates(params, updates)
                if has_aux:
                    return (params, opt_state, grads, *out)
                return params, opt_state, grads, out

        # (loss_fn, optimizer, mode, wire) is the static config: two
        # wrappers building the same train step share one compiled program
        raw_step = step
        raw_step.__name__ = "dp_train_step"  # the program's name in a device trace
        n_state = 2 if self.blocking_parameter_updates else 3
        compiled = program_cache.cached_program(
            "dp_train_step",
            (loss_fn, optimizer, self.blocking_parameter_updates, wire, has_aux, state_rule),
            lambda: raw_step,
            comm=self.comm,
            donate=range(n_state),
        )
        self._train_step = compiled

        def train_step(*args):
            with telemetry.span("heat_tpu.train.step"):
                with telemetry.span("heat_tpu.train.step.prepare"):
                    batch = tuple(
                        a if isinstance(a, jax.Array) else self.shard_batch(a)[0]
                        for a in args[n_state:]
                    )
                with telemetry.span("heat_tpu.train.step.launch") as launch:
                    out = compiled(*args[:n_state], *batch)
                    if launch.recording:  # telemetry on or a profile live: the program can be asked for its scopes
                        # after the call, which may have traced anew; a donated array still says its shape and placement
                        telemetry.hlo.note_launch("dp_train_step", compiled, (*args[:n_state], *batch))
                    return out

        train_step.lower = compiled.lower
        return train_step

    def _make_compressed_step(self, loss_fn, optimizer, wire: str):
        """The shard_map form of the train step whose gradient collective
        moves a compressed payload (``wire`` in bf16/int8/blockwise).
        Non-float gradient leaves (rare, e.g. integer counters) pass
        through an exact pmean."""
        from ..core import collective_prec

        comm = self.comm
        axis = comm.axis_name
        p = comm.size
        blocking = self.blocking_parameter_updates
        block = collective_prec.block_size()

        def grad_mean(g):
            if not collective_prec.compressible(g.dtype):
                return jax.lax.pmean(g, axis)
            return collective_prec.pmean(g, axis, p, wire, block)

        # check_vma=False below: the compressed mean ends in an
        # all-gather, so every position holds the same gradients, but the
        # varying-axis checker cannot infer that replication. The kernel
        # also relies on the unchecked mode for its arithmetic: checked,
        # the gradient of a P() parameter comes back already summed over
        # the axis and grad_mean would count it p times. Both are pinned
        # in tests/test_collective_prec.py (the compressed trajectory
        # tracks the exact step; every position returns the same bits).

        def kernel_body(params, opt_state, batch):
            # local grads of the local-batch mean loss; the global mean
            # over equal shards is the pmean of the local means (the
            # shard_batch contract forbids uneven/padded batches)
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            loss = jax.lax.pmean(loss, axis)
            grads = jax.tree.map(grad_mean, grads)
            return loss, grads

        if blocking:

            def kernel(params, opt_state, *batch):
                loss, grads = kernel_body(params, opt_state, batch)
                updates, opt_state = optimizer.update(
                    grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            def step(params, opt_state, *batch):
                in_specs = (P(), P()) + (P(axis),) * len(batch)
                return jax.shard_map(
                    kernel, mesh=comm.mesh, in_specs=in_specs,
                    out_specs=(P(), P(), P()), check_vma=False,
                )(params, opt_state, *batch)

        else:

            def kernel(params, opt_state, pending_grads, *batch):
                loss, grads = kernel_body(params, opt_state, batch)
                updates, opt_state = optimizer.update(
                    pending_grads, opt_state, params
                )
                params = optax.apply_updates(params, updates)
                return params, opt_state, grads, loss

            def step(params, opt_state, pending_grads, *batch):
                if jax.tree_util.tree_structure(
                    pending_grads
                ) != jax.tree_util.tree_structure(params):
                    raise TypeError(
                        "non-blocking (double-buffered) DataParallel step "
                        "signature is step(params, opt_state, pending_grads,"
                        " *batch) -> (params, opt_state, next_pending, "
                        "loss); seed pending_grads with "
                        "DataParallel.init_pending(params)"
                    )
                in_specs = (P(), P(), P()) + (P(axis),) * len(batch)
                return jax.shard_map(
                    kernel, mesh=comm.mesh, in_specs=in_specs,
                    out_specs=(P(), P(), P(), P()), check_vma=False,
                )(params, opt_state, pending_grads, *batch)

        return step

    @staticmethod
    def init_pending(params):
        """Zero gradient buffer seeding the double-buffered loop (the
        reference's iteration-0 zero-return, data_parallel.py:276)."""
        return jax.tree_util.tree_map(jnp.zeros_like, params)


class DataParallelMultiGPU:
    """Hierarchical data parallelism paired with DASO (reference
    data_parallel.py:314-376).

    The reference wraps the model in node-local torch DDP (NCCL fast domain)
    and leaves the slow inter-node domain to DASO over MPI. The TPU analog:
    DASO owns a 2-level mesh (``local`` axis ≈ ICI/NCCL, ``node`` axis ≈
    DCN/MPI); this wrapper binds the module's loss to that schedule via
    ``daso.set_model``.
    """

    def __init__(self, module, daso):
        self.module = module
        self.apply_fn = _module_apply(module)
        self.daso = daso
        daso.set_model(module)

    def __call__(self, params, *inputs):
        return self.apply_fn(params, *inputs)
