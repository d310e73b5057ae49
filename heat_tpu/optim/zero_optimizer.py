"""ZeRO-style optimizer-state sharding (arXiv:2004.13336, ISSUE 15).

:class:`DataParallelOptimizer` replicates optimizer state on every mesh
position — for Adam that is 2× the parameter bytes *per replica*, pure
redundancy: every replica computes the identical update. ZeRO stage 1
shards the state (and the update compute) across the data-parallel axis
instead: position ``i`` owns the flat 1/p chunk ``[i·c, (i+1)·c)`` of
every leaf (:func:`heat_tpu.parallel.fsdp.flat_shard_pytree`), and one
step is

    reduce-scatter grads → local shard update → all-gather params

— the memory freed (a strictly lower optimizer-state live-bytes
watermark, pinned by ``tests/test_zero_optimizer.py``) is what funds
bigger per-replica batches at scale. Both collectives ride the
:class:`~heat_tpu.core.communication.MeshCommunication` wrappers, so
they inherit the ISSUE 9 wire compression (the gradient reduce-scatter
honors ``precision=``; the parameter all-gather pins exact — compressed
parameters would change the model) AND the ISSUE 15 tiered lowering:
under ``HEAT_TPU_HIERARCHICAL=1`` the gradient reduce-scatter is
in-node exact + cross-node compressed, which is exactly the
DASO/hierarchy composition ROADMAP item 3 calls for.

Update arithmetic is elementwise for the supported optax transforms
(sgd/momentum/adam/rmsprop — anything whose state leaves follow the
parameter shapes), so the trajectory is identical to
:class:`DataParallelOptimizer` applying the same globally-averaged
gradients — per element, bit-for-bit on the same backend (the parity
oracle in tests).

Checkpointing rides :mod:`heat_tpu.resilience`: the sharded state is
gathered to its *logical* (unpadded) form before the blobs are written,
so a checkpoint taken on one topology restores bit-exactly on another —
the elastic-resume seed (restore re-pads and re-shards for the new mesh).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..core import program_cache
from ..core.communication import MeshCommunication, sanitize_comm
from ..parallel import fsdp
from .dp_optimizer import DataParallelOptimizer

__all__ = ["ZeroOptimizer"]


class ZeroOptimizer(DataParallelOptimizer):
    """Optimizer-state sharding over the communicator's flat mesh axis.

    Parameters
    ----------
    optimizer : optax.GradientTransformation
        The local transform. Its state leaves must follow the parameter
        shapes (elementwise transforms: sgd, momentum, adam, rmsprop…) —
        the sharded update is computed per flat chunk.
    comm : MeshCommunication, optional
        Mesh whose single axis is the data-parallel axis.
    precision : str, optional
        Wire mode of the gradient reduce-scatter (ISSUE 9 vocabulary),
        resolved ONCE at construction — flat
        ``HEAT_TPU_COLLECTIVE_PREC`` semantics, or the cross-node tier
        under ``HEAT_TPU_HIERARCHICAL=1``. Pinned at construction
        because the blockwise chunk padding is part of the state
        *layout*: changing the wire mode means building a new
        ZeroOptimizer (and re-initializing or restoring state).
    """

    def __init__(self, optimizer, comm: Optional[MeshCommunication] = None,
                 precision: Optional[str] = None):
        super().__init__(optimizer)
        self.comm = sanitize_comm(comm)
        from ..core import collective_prec, topology

        if topology.active(self.comm.size) is not None:
            self._wire = topology.cross_mode(jnp.float32, precision)
        else:
            self._wire = collective_prec.effective(jnp.float32, precision)
        self._block = collective_prec.block_size()

    # -- state layout ---------------------------------------------------------

    def _chunk(self, numel: int) -> int:
        return fsdp.flat_chunk(numel, self.comm.size, self._wire, self._block)

    def _flat_pad(self, leaf):
        """Traced helper: one leaf flattened and zero-padded to
        ``p · chunk`` (the layout every collective and slice agrees on)."""
        p = self.comm.size
        c = self._chunk(leaf.size)
        flat = leaf.reshape(-1)
        if p * c != leaf.size:
            flat = jnp.pad(flat, (0, p * c - leaf.size))
        return flat

    def init(self, params):
        """Sharded optimizer state: ``optimizer.init`` on the flat
        ``(p, chunk)`` leaves, every following-shape state leaf pinned
        sharded along axis 0 (scalars — step counts — replicate)."""
        flat = fsdp.flat_shard_pytree(
            params, self.comm, self._wire, self._block
        )
        return self.init_from_shards(flat)

    def init_from_shards(self, flat_params):
        """:meth:`init` for parameters ALREADY in the flat ``(p, chunk)``
        layout — the composition point full FSDP (ISSUE 18) builds on:
        sharded optimizer state over parameters that are themselves
        persistent shards, without a round-trip through the logical
        form."""
        comm = self.comm
        flat = flat_params
        opt = self.optimizer
        p = comm.size

        def build():
            def init_fn(fp):
                state = opt.init(fp)
                return jax.tree.map(
                    lambda l: jax.lax.with_sharding_constraint(
                        l, comm.sharding(0, l.ndim)
                    )
                    if getattr(l, "ndim", 0) == 2 and l.shape[0] == p
                    else l,
                    state,
                )

            return init_fn

        return program_cache.cached_program(
            "zero_opt_init", (opt, self._wire, self._block), build,
            comm=comm,
        )(flat)

    # -- the sharded step -----------------------------------------------------

    def _state_specs(self, opt_state):
        from jax.sharding import PartitionSpec as P

        axis = self.comm.axis_name
        p = self.comm.size
        return jax.tree.map(
            lambda l: P(axis)
            if getattr(l, "ndim", 0) == 2 and l.shape[0] == p
            else P(),
            opt_state,
        )

    def _shard_update(self, my_p, my_s, my_g):
        """One position's chunk update: squeeze the local (1, chunk)
        state rows, apply the transform, re-stack."""
        s_local = jax.tree.map(
            lambda s: s[0] if getattr(s, "ndim", 0) == 2 else s, my_s
        )
        updates, s_new = self.optimizer.update(my_g, s_local, my_p)
        p_new = optax.apply_updates(my_p, updates)
        s_new = jax.tree.map(
            lambda s: s[None] if getattr(s, "ndim", 0) == 1 else s, s_new
        )
        return p_new, s_new

    # public alias: the per-chunk update IS the ZeRO/FSDP composition
    # surface (heat_tpu.nn.FSDP reuses the same chunk arithmetic), so it
    # is part of the supported API, not an implementation detail
    shard_update = _shard_update

    def _gather_params(self, local_new, params_template):
        """all-gather each updated chunk back to the replicated logical
        leaf. Parameters pin ``precision='off'`` — a compressed gather
        would change the model every step."""
        comm = self.comm

        def gather(loc, orig):
            g = comm.all_gather(loc, precision="off")       # (p·chunk,)
            return g[: orig.size].reshape(orig.shape).astype(orig.dtype)

        return jax.tree.map(gather, local_new, params_template)

    def step(self, params, opt_state, grads) -> Tuple[Any, Any]:
        """Drop-in :class:`DataParallelOptimizer` form: ``grads`` are the
        already-averaged (replicated) gradients, so no reduce-scatter is
        needed — each position slices its chunk, updates its state
        shard, and one all-gather rebuilds the parameters. Returns
        ``(params, opt_state)``."""
        from jax.sharding import PartitionSpec as P

        comm = self.comm
        axis = comm.axis_name
        p = comm.size
        me = self

        def build():
            def kernel(params, opt_state, grads):
                r = jax.lax.axis_index(axis)

                def slice_leaf(l):
                    c = me._chunk(l.size)
                    return jax.lax.dynamic_slice(
                        me._flat_pad(l), (r * c,), (c,)
                    )

                my_p = jax.tree.map(slice_leaf, params)
                my_g = jax.tree.map(slice_leaf, grads)
                p_new, s_new = me._shard_update(my_p, opt_state, my_g)
                return me._gather_params(p_new, params), s_new

            def step_fn(params, opt_state, grads):
                specs_s = me._state_specs(opt_state)
                return jax.shard_map(
                    kernel, mesh=comm.mesh,
                    in_specs=(P(), specs_s, P()),
                    out_specs=(P(), specs_s),
                    # the parameter all-gather leaves every position
                    # with the same values; the varying-axis checker
                    # cannot infer that replication
                    check_vma=False,
                )(params, opt_state, grads)

            return step_fn

        # _block is part of the key: it sets the blockwise chunk layout
        # the kernel's slices are traced against. The tiered-lowering
        # token is appended by program_key itself — not repeated here.
        compiled = program_cache.cached_program(
            "zero_step", (self.optimizer, self._wire, self._block),
            build, comm=comm,
        )
        return compiled(params, opt_state, grads)

    def make_train_step(self, loss_fn: Callable) -> Callable:
        """The full ZeRO train step (the paper's form): batch sharded
        along axis 0, per-position ``value_and_grad`` of the local-shard
        mean loss, gradient MEAN via the wrappers' reduce-scatter (wire
        mode = this instance's pinned ``precision``; tiered under
        ``HEAT_TPU_HIERARCHICAL=1``), shard update, parameter
        all-gather. Returns ``step(params, opt_state, *batch) ->
        (params, opt_state, loss)``; batch arrays must be evenly
        sharded (``DataParallel.shard_batch`` contract)."""
        from jax.sharding import PartitionSpec as P

        comm = self.comm
        axis = comm.axis_name
        p = comm.size
        wire = self._wire
        me = self

        def build():
            def kernel(params, opt_state, *batch):
                # local gradients: under check_vma=False (below) the
                # gradient of a P() parameter is not summed over the axis
                # behind our back, so the reduce-scatter is the only sum
                loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
                loss = comm.psum(loss, precision="off") / p

                def rs_mean(g):
                    # reduce-scatter returns this chunk of the SUM over
                    # positions; the pre-padded flat layout keeps the
                    # compressed chunk boundaries on the state shards
                    return comm.reduce_scatter(
                        me._flat_pad(g), precision=wire
                    ) / p

                my_g = jax.tree.map(rs_mean, grads)
                r = jax.lax.axis_index(axis)

                def slice_leaf(l):
                    c = me._chunk(l.size)
                    return jax.lax.dynamic_slice(
                        me._flat_pad(l), (r * c,), (c,)
                    )

                my_p = jax.tree.map(slice_leaf, params)
                p_new, s_new = me._shard_update(my_p, opt_state, my_g)
                return me._gather_params(p_new, params), s_new, loss

            def step_outer(params, opt_state, *batch):
                specs_s = me._state_specs(opt_state)
                in_specs = (P(), specs_s) + (P(axis),) * len(batch)
                return jax.shard_map(
                    kernel, mesh=comm.mesh,
                    in_specs=in_specs,
                    out_specs=(P(), specs_s, P()),
                    # as in step(): gathered params. The P() outputs
                    # being one value on every position is pinned in
                    # tests/test_zero_optimizer.py
                    check_vma=False,
                )(params, opt_state, *batch)

            return step_outer

        return program_cache.cached_program(
            "zero_train_step",
            (self.optimizer, loss_fn, wire, self._block),
            build, comm=comm,
        )

    # -- memory accounting ----------------------------------------------------

    def state_bytes_per_device(self, opt_state) -> int:
        """Worst-case per-device live bytes of the sharded state — the
        figure the watermark oracle compares against the replicated
        :class:`DataParallelOptimizer` state (strictly lower for any
        mesh with p > 1 and a non-trivial state)."""
        per_dev: dict = {}
        for leaf in jax.tree.leaves(opt_state):
            if not hasattr(leaf, "addressable_shards"):
                continue
            for sh in leaf.addressable_shards:
                d = str(sh.device)
                per_dev[d] = per_dev.get(d, 0) + sh.data.nbytes
        return max(per_dev.values()) if per_dev else 0

    # -- checkpoint / restore (resilience, the elastic-resume seed) -----------

    def _logical_state(self, params, opt_state):
        """The topology-independent form: every sharded ``(p, chunk)``
        leaf unpadded back to its logical parameter shape (scalars pass
        through). Pairing is by tree position against an ``eval_shape``
        template of ``optimizer.init`` on the LOGICAL leaves — valid for
        any shape-following transform."""
        template = jax.eval_shape(self.optimizer.init, params)
        t_leaves, tdef = jax.tree_util.tree_flatten(template)
        s_leaves = jax.tree_util.tree_flatten(opt_state)[0]

        out = []
        for t, s in zip(t_leaves, s_leaves):
            if getattr(s, "ndim", 0) == 2 and tuple(s.shape) != tuple(t.shape):
                out.append(fsdp.flat_unshard_leaf(s, t.shape, t.dtype))
            else:
                import numpy as np

                out.append(np.asarray(s))
        return jax.tree_util.tree_unflatten(tdef, out)

    def _shard_logical_state(self, logical_state):
        """Re-pad + re-shard a logical state tree onto THIS mesh."""
        comm = self.comm
        p = comm.size

        def shard(l):
            l = jnp.asarray(l)
            if l.ndim == 0:
                return jax.device_put(l, comm.replicated())
            c = self._chunk(l.size)
            flat = l.reshape(-1)
            if p * c != l.size:
                flat = jnp.pad(flat, (0, p * c - l.size))
            return jax.device_put(flat.reshape(p, c), comm.sharding(0, 2))

        return jax.tree.map(shard, logical_state)

    def save_checkpoint(self, path: str, params, opt_state) -> str:
        """Checkpoint (params, logical opt state) — per-shard blobs,
        CRC-checked, atomically swapped
        (:mod:`heat_tpu.resilience.checkpoint`). The state is stored
        UNPADDED, so the blobs carry no trace of this mesh's size."""
        from .. import resilience

        logical = self._logical_state(params, opt_state)
        return resilience.save_checkpoint(
            {"params": params, "opt_state": logical}, path,
            extra={"algo": "zero", "wire": self._wire},
        )

    def load_checkpoint(self, path: str, params):
        """Restore a :meth:`save_checkpoint` directory onto THIS
        instance's mesh: the logical state re-pads and re-shards for the
        current topology, bit-exactly — a job restarted on a different
        mesh size continues the same trajectory. ``params`` supplies the
        tree structure. Returns ``(params, opt_state)``."""
        from .. import resilience

        template = jax.eval_shape(self.optimizer.init, params)
        tree, extra = resilience.load_checkpoint(
            path, like={"params": params, "opt_state": template},
            with_extra=True,
        )
        if extra.get("algo") != "zero":
            raise resilience.CheckpointError(
                f"{path!r} is a {extra.get('algo')!r} checkpoint, not zero"
            )
        restored = jax.tree.map(
            lambda l: jax.device_put(jnp.asarray(l), self.comm.replicated()),
            tree["params"],
        )
        return restored, self._shard_logical_state(tree["opt_state"])
