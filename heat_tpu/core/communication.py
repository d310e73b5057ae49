"""Mesh-backed communication layer — the TPU-native replacement for MPI.

The reference routes *all* inter-process traffic through hand-written MPI
calls (reference: heat/core/communication.py:120-1864, `MPICommunication`
wrapping an `MPI.Comm` with Send/Recv, Bcast, Allreduce, Allgatherv,
Alltoall(v/w), Scatterv/Gatherv, derived datatypes and GPU staging buffers).
On TPU none of that choreography survives: a :class:`Communication` here wraps
a :class:`jax.sharding.Mesh` over the chips of one platform, arrays are
sharded `jax.Array`s, and XLA emits the collectives (over ICI within a slice,
DCN across slices) from sharding annotations. What remains of the reference
layer — and what this module provides — is:

* the **chunk arithmetic** that defines which global indices each mesh
  position owns (`chunk`, `lshape_map`, `counts_displs`); the reference's
  balanced rule (communication.py:161-209: ``n//p`` with the first ``n%p``
  ranks one larger) is replaced by the **ceil rule** (``ceil(n/p)`` per shard,
  short/empty tail shards) because that is the physical layout XLA uses for a
  sharded dimension; arrays whose split dimension is not divisible are stored
  **tail-padded** to ``ceil(n/p)*p`` (see dndarray.py for the invariant);
* `NamedSharding` factories translating Heat's single ``split`` axis into
  `PartitionSpec`s over the mesh;
* explicit in-`shard_map` collectives (`psum`, `all_gather`, `ppermute`,
  `all_to_all`) for the few kernels where we hand-schedule (ring cdist, TSQR),
  mirroring the reference inventory in spirit;
* the global communicator registry (`WORLD` analog, `get_comm`/`use_comm`,
  reference communication.py:1867-1914).
"""

from __future__ import annotations

import math
import os
from typing import List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .devices import Device, get_device
from .. import resilience, telemetry

__all__ = [
    "Communication",
    "MeshCommunication",
    "get_comm",
    "init_distributed",
    "sanitize_comm",
    "use_comm",
    "to_varying",
    "CommunicationError",
]


def to_varying(x, axis: str):
    """Type ``x`` as device-varying over mesh axis ``axis`` for
    ``shard_map``'s varying-axis checker (fresh accumulators are
    replicated; a loop carry that mixes with sharded values must vary).
    A value that already varies passes through: ``pcast`` refuses a
    varying→varying cast."""
    if axis in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, (axis,), to="varying")


class CommunicationError(RuntimeError):
    pass


class Communication:
    """Abstract base (reference communication.py:88-117)."""

    @staticmethod
    def is_distributed() -> bool:
        raise NotImplementedError()

    def chunk(self, shape, split, rank=None):
        raise NotImplementedError()


class MeshCommunication(Communication):
    """A communicator backed by a 1-D device mesh.

    ``size`` is the number of mesh positions (devices), the analog of the MPI
    world size; ``rank`` is the host process index (0 in single-controller
    runs — per-shard identity lives inside `shard_map` kernels as the mesh
    axis index, not in Python).

    Parameters
    ----------
    devices : sequence of jax.Device, optional
        Devices to build the mesh over. Defaults to all devices of the
        current default platform.
    axis : str
        Mesh axis name used in PartitionSpecs (default ``"proc"``).
    """

    def __init__(
        self,
        devices: Optional[Sequence["jax.Device"]] = None,
        axis: str = "proc",
        device: Optional[Device] = None,
    ):
        if devices is None:
            dev = device if device is not None else get_device()
            devices = dev.jax_devices()
        self.__devices = list(devices)
        self.__axis = axis
        self.__first_local_position = None
        self.__mesh = Mesh(np.asarray(self.__devices), (axis,))

    # -- identity ------------------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        return self.__mesh

    @property
    def axis_name(self) -> str:
        return self.__axis

    @property
    def size(self) -> int:
        """Number of mesh positions — the world size analog."""
        return len(self.__devices)

    @property
    def rank(self) -> int:
        """Host process index (0 under single-controller JAX)."""
        return jax.process_index()

    def first_local_position(self) -> int:
        """Mesh position of this process's first device — the position whose
        chunk `DNDarray.lshape` reports (on a single controller: 0).

        Fixed for the mesh's lifetime, so the device-list scan runs once
        (`lshape` consults this on every access)."""
        cached = self.__first_local_position
        if cached is None:
            pidx = jax.process_index()
            cached = 0
            for i, dev in enumerate(self.__devices):
                if dev.process_index == pidx:
                    cached = i
                    break
            self.__first_local_position = cached
        return cached

    @property
    def devices(self) -> List["jax.Device"]:
        return list(self.__devices)

    @staticmethod
    def is_distributed() -> bool:
        return jax.process_count() > 1

    def __eq__(self, other):
        if isinstance(other, MeshCommunication):
            return self.__devices == other.devices and self.__axis == other.axis_name
        return NotImplemented

    def __hash__(self):
        return hash((tuple(self.__devices), self.__axis))

    def __repr__(self):
        plat = self.__devices[0].platform if self.__devices else "?"
        return f"MeshCommunication(size={self.size}, axis={self.__axis!r}, platform={plat!r})"

    # -- chunk arithmetic (the layout contract) ------------------------------

    def chunk_size(self, n: int) -> int:
        """Per-position physical chunk length for a dimension of logical
        length ``n``: ``ceil(n/size)`` (the XLA shard size)."""
        if self.size == 0:
            return n
        return -(-n // self.size)

    def padded_size(self, n: int) -> int:
        """Physical (padded) global length: ``chunk_size * size``."""
        return self.chunk_size(n) * self.size

    def padded_shape(self, gshape: Sequence[int], split: Optional[int]) -> Tuple[int, ...]:
        """Physical storage shape for a logical global shape: identical except
        the split dimension is rounded up to a multiple of ``size``."""
        gshape = tuple(int(s) for s in gshape)
        if split is None:
            return gshape
        return gshape[:split] + (self.padded_size(gshape[split]),) + gshape[split + 1 :]

    def chunk(
        self,
        shape: Sequence[int],
        split: Optional[int],
        rank: Optional[int] = None,
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Logical sub-chunk of mesh position ``rank`` (default: all identical
        when split is None). Returns ``(offset, local_shape, slices)`` —
        same contract as the reference (communication.py:161-209) but with the
        ceil distribution rule: position ``r`` owns global indices
        ``[r*c, min((r+1)*c, n))`` with ``c = ceil(n/size)``; tail positions
        may own empty ranges."""
        shape = tuple(int(s) for s in shape)
        dims = len(shape)
        if split is None:
            return 0, shape, tuple(slice(0, end) for end in shape)
        if rank is None:
            rank = 0
        n = shape[split]
        c = self.chunk_size(n)
        start = min(rank * c, n)
        end = min((rank + 1) * c, n)
        lshape = shape[:split] + (end - start,) + shape[split + 1 :]
        slices = tuple(
            slice(start, end) if d == split else slice(0, shape[d]) for d in range(dims)
        )
        return start, lshape, slices

    def lshape_map(self, gshape: Sequence[int], split: Optional[int]) -> np.ndarray:
        """(size, ndim) int array of every position's logical chunk shape
        (reference dndarray.py:222 `lshape_map` property)."""
        out = np.empty((self.size, len(gshape)), dtype=np.int64)
        for r in range(self.size):
            _, lshape, _ = self.chunk(gshape, split, r)
            out[r] = lshape
        return out

    def counts_displs(self, n: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Per-position logical counts and displacements along a split
        dimension of length ``n`` (reference dndarray.py:552)."""
        c = self.chunk_size(n)
        counts = tuple(max(0, min((r + 1) * c, n) - min(r * c, n)) for r in range(self.size))
        displs = tuple(min(r * c, n) for r in range(self.size))
        return counts, displs

    # -- sharding factories --------------------------------------------------

    def spec(self, split: Optional[int], ndim: int) -> PartitionSpec:
        """PartitionSpec placing the mesh axis on dimension ``split``."""
        if split is None:
            return PartitionSpec()
        axes = [None] * ndim
        axes[split] = self.__axis
        return PartitionSpec(*axes)

    def sharding(self, split: Optional[int], ndim: int) -> NamedSharding:
        """NamedSharding for a DNDarray with the given split."""
        return NamedSharding(self.__mesh, self.spec(split, ndim))

    def replicated(self, ndim: int = 0) -> NamedSharding:
        return NamedSharding(self.__mesh, PartitionSpec())

    # -- collective cost model ----------------------------------------------

    def relayout_cost(
        self,
        gshape: Sequence[int],
        itemsize: int,
        old_split: Optional[int],
        new_split: Optional[int],
        precision: str = "off",
    ) -> "telemetry.collectives.CollectiveCost":
        """Analytic collective kind + wire bytes of a relayout on this mesh
        (telemetry/collectives.py — the observability analog of the
        reference's explicit Alltoallv volume). ``precision`` prices the
        compressed-wire program (ISSUE 9); callers pass the *effective*
        wire mode they resolved for the payload's dtype."""
        from . import collective_prec

        return telemetry.collectives.relayout_cost(
            gshape, itemsize, old_split, new_split, self.size,
            precision=precision, block=collective_prec.block_size(),
        )

    # -- 2-level topology (ISSUE 15) -----------------------------------------

    def topology(self):
        """The resolved 2-level ``(node, local)`` factorization of this
        mesh (:mod:`heat_tpu.core.topology`): the ``HEAT_TPU_TOPOLOGY``
        knob when declared, else auto-detection (host-process structure
        on real hardware; the DASO-style emulated two-node split on a
        single even host mesh). Resolved per call — the knob may change
        between traces."""
        from . import topology as _topo

        return _topo.resolve(self.size)

    def _hier(self):
        """The topology to lower tiered against, or None for flat:
        requires ``HEAT_TPU_HIERARCHICAL=1`` and a nontrivial
        factorization."""
        from . import topology as _topo

        return _topo.active(self.size)

    def hier_token(self):
        """The tiered-lowering program-cache key component
        (:func:`heat_tpu.core.topology.cache_token`). Callers caching
        programs built over the payload-moving wrappers must include
        this alongside ``collective_prec.effective(dtype)`` — same
        contract, same reason."""
        from . import topology as _topo

        return _topo.cache_token(self.size)

    def _cross_wire(self, x, precision: Optional[str]) -> str:
        """The cross-node tier's wire mode for one payload (per-call
        override → ``HEAT_TPU_HIERARCHICAL_PREC`` →
        ``HEAT_TPU_COLLECTIVE_PREC``; off for non-floats)."""
        from . import topology as _topo

        return _topo.cross_mode(x.dtype, precision)

    # -- explicit collectives (for hand-written shard_map kernels) -----------
    # These are thin curried wrappers so kernels don't hard-code axis names.
    # With telemetry enabled each wrapper records a trace-time event: the
    # wrappers run while a shard_map/jit body is being TRACED, so the event
    # stream names the collectives that entered a compiled program. A hot
    # cached program emits nothing — but a caller that builds a fresh
    # traced closure per invocation (the ring kernels) misses the cache
    # and re-emits on every call, so trace-event counts are per-trace,
    # not per-program.
    #
    # ``precision`` (ISSUE 9, HEAT_TPU_COLLECTIVE_PREC): every payload-
    # moving wrapper compresses its wire payload under the resolved mode
    # (global knob, or the per-call override). Float payloads only —
    # integer/bool payloads (indices, counts, sort keys) always move
    # exact — and exactness-critical kernels pin ``precision="off"`` at
    # their call site. The wire mode is part of the traced program, so
    # callers caching programs built over these wrappers must key on
    # ``collective_prec.effective(dtype)``.

    def _coll(self, name: str, fn, *args, **kwargs):
        """One collective wrapper body: with the resilience subsystem armed
        (ISSUE 5), the lax call runs under the fault injector + transient-
        retry guard at site ``collective.<name>`` — the wrappers execute
        while a program is being *traced*, so a retried transient simply
        re-issues the lax op into the same trace (nothing recompiles).
        Disarmed, the cost is one flag check."""
        if resilience.armed():
            return resilience.guarded_call(f"collective.{name}", fn, args, kwargs)
        return fn(*args, **kwargs)

    def _wire(self, x, precision: Optional[str]) -> str:
        """The effective wire mode for one payload (off for non-floats)."""
        from . import collective_prec

        return collective_prec.effective(x.dtype, precision)

    def psum(self, x, precision: Optional[str] = None):
        from . import collective_prec

        topo = self._hier()
        if topo is not None:
            from . import topology as _topo

            wire = self._cross_wire(x, precision)
            telemetry.trace_event(
                "psum", axis=self.__axis, wire=wire, hier=topo.describe(),
                **telemetry.collectives.hierarchical_allreduce_cost(
                    x.size, x.dtype.itemsize, topo.node, topo.local,
                    wire, collective_prec.block_size(),
                ).as_fields(),
            )
            return self._coll(
                "psum", _topo.hier_psum, x, self.__axis, topo, wire,
                collective_prec.block_size(),
            )
        wire = self._wire(x, precision)
        telemetry.trace_event("psum", axis=self.__axis, wire=wire)
        if wire != "off":
            return self._coll(
                "psum", collective_prec.psum, x, self.__axis, self.size, wire,
            )
        return self._coll("psum", jax.lax.psum, x, self.__axis)

    def reduce_scatter(self, x, precision: Optional[str] = None):
        """Reduce-scatter of this payload, flattened: position ``i``
        returns the 1-D ``(ceil(numel/p),)`` chunk ``i`` of the global
        sum (the ZeRO gradient primitive — arXiv:2004.13336). Flat it is
        one ``psum_scatter`` (quantized modes: the EQuARX first phase);
        tiered it is in-node reduce-scatter (exact) then cross-node
        reduce-scatter of the 1/local shard (``precision`` compresses
        the cross tier only)."""
        from . import collective_prec

        topo = self._hier()
        if topo is not None:
            from . import topology as _topo

            wire = self._cross_wire(x, precision)
            telemetry.trace_event(
                "reduce_scatter", axis=self.__axis, wire=wire,
                hier=topo.describe(),
                **telemetry.collectives.hierarchical_reduce_scatter_cost(
                    x.size, x.dtype.itemsize, topo.node, topo.local,
                    wire, collective_prec.block_size(),
                ).as_fields(),
            )
            return self._coll(
                "reduce_scatter", _topo.hier_reduce_scatter, x,
                self.__axis, topo, wire, collective_prec.block_size(),
            )
        wire = self._wire(x, precision)
        telemetry.trace_event(
            "reduce_scatter", axis=self.__axis, wire=wire
        )
        return self._coll(
            "reduce_scatter", collective_prec.reduce_scatter, x,
            self.__axis, self.size, wire,
        )

    def pmax(self, x):
        # extremes are exactness-critical (argmin/argmax tie-breaking,
        # guard thresholds) — never compressed
        telemetry.trace_event("pmax", axis=self.__axis)
        return self._coll("pmax", jax.lax.pmax, x, self.__axis)

    def pmin(self, x):
        telemetry.trace_event("pmin", axis=self.__axis)
        return self._coll("pmin", jax.lax.pmin, x, self.__axis)

    def axis_index(self):
        return jax.lax.axis_index(self.__axis)

    def all_gather(self, x, tiled: bool = True,
                   precision: Optional[str] = None):
        from . import collective_prec

        topo = self._hier()
        if topo is not None:
            from . import topology as _topo

            wire = self._cross_wire(x, precision)
            telemetry.trace_event(
                "all_gather", axis=self.__axis, wire=wire,
                hier=topo.describe(),
                **telemetry.collectives.hierarchical_allgather_cost(
                    x.size, x.dtype.itemsize, topo.node, topo.local,
                    wire, collective_prec.block_size(),
                ).as_fields(),
            )
            return self._coll(
                "all_gather", _topo.hier_all_gather, x, self.__axis,
                topo, wire, collective_prec.block_size(), tiled=tiled,
            )
        wire = self._wire(x, precision)
        telemetry.trace_event("all_gather", axis=self.__axis, wire=wire)
        if wire != "off":
            return self._coll(
                "all_gather", collective_prec.all_gather, x, self.__axis,
                wire, tiled=tiled,
            )
        return self._coll("all_gather", jax.lax.all_gather, x, self.__axis, tiled=tiled)

    def ppermute(self, x, perm, precision: Optional[str] = None):
        from . import collective_prec

        wire = self._wire(x, precision)
        telemetry.trace_event("ppermute", axis=self.__axis, wire=wire)
        if wire != "off":
            return self._coll(
                "ppermute", collective_prec.ppermute, x, self.__axis, perm,
                wire,
            )
        return self._coll("ppermute", jax.lax.ppermute, x, self.__axis, perm=perm)

    def ring_permute(self, x, shift: int = 1,
                     precision: Optional[str] = None):
        """Circulate shards around the ring: position i sends to i+shift."""
        n = self.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        from . import collective_prec

        wire = self._wire(x, precision)
        telemetry.trace_event(
            "ppermute", axis=self.__axis, ring_shift=shift, wire=wire
        )
        if wire != "off":
            return self._coll(
                "ppermute", collective_prec.ppermute, x, self.__axis, perm,
                wire,
            )
        return self._coll("ppermute", jax.lax.ppermute, x, self.__axis, perm=perm)

    def all_to_all(self, x, split_axis: int, concat_axis: int,
                   precision: Optional[str] = None):
        from . import collective_prec

        topo = self._hier()
        if topo is not None:
            from . import topology as _topo

            wire = self._cross_wire(x, precision)
            phys = x.size * self.size  # per-shard payload × participants
            telemetry.trace_event(
                "all_to_all", axis=self.__axis, wire=wire,
                hier=topo.describe(),
                **telemetry.collectives.hierarchical_a2a_cost(
                    phys, x.dtype.itemsize, topo.node, topo.local,
                    wire, collective_prec.block_size(),
                ).as_fields(),
            )
            return self._coll(
                "all_to_all", _topo.hier_all_to_all, x, self.__axis,
                topo, split_axis, concat_axis, wire,
                collective_prec.block_size(),
            )
        wire = self._wire(x, precision)
        telemetry.trace_event("all_to_all", axis=self.__axis, wire=wire)
        if wire != "off":
            return self._coll(
                "all_to_all", collective_prec.all_to_all, x, self.__axis,
                self.size, split_axis, concat_axis, wire,
            )
        return self._coll(
            "all_to_all", jax.lax.all_to_all, x, self.__axis,
            split_axis=split_axis, concat_axis=concat_axis, tiled=True,
        )


# -- global communicator registry --------------------------------------------

__default_comm: Optional[MeshCommunication] = None


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> MeshCommunication:
    """Bootstrap the multi-host runtime and rebuild the default communicator
    over the full global device set (SURVEY §7 stage 1; the analog of the
    reference's ``mpirun`` launch + ``MPI_WORLD`` construction, reference
    communication.py:1867).

    Call once per host process before any array construction. On managed
    TPU pods the arguments are auto-detected from the environment
    (``jax.distributed.initialize()`` with no args); on manual clusters pass
    the coordinator's ``host:port``, the world size, and this process's
    rank. After initialization the default communicator's mesh spans every
    device of every host, sharded collectives ride ICI within a slice and
    DCN across hosts, and ``comm.rank``/``jax.process_index()`` report this
    host's rank."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    comm = MeshCommunication()
    use_comm(comm)
    return comm


def get_comm() -> MeshCommunication:
    """The globally-set default communicator (reference communication.py:1874).

    Built lazily over all devices of the default platform so that test
    harnesses can select the CPU platform before first use."""
    global __default_comm
    if __default_comm is None:
        __default_comm = MeshCommunication()
    return __default_comm


def use_comm(comm: Optional[MeshCommunication] = None) -> None:
    """Set the globally-used default communicator (reference
    communication.py:1904)."""
    global __default_comm
    if comm is not None and not isinstance(comm, MeshCommunication):
        raise TypeError(f"Unknown communication, must be MeshCommunication, got {comm!r}")
    __default_comm = comm if comm is not None else MeshCommunication()


def sanitize_comm(comm: Optional[Communication]) -> MeshCommunication:
    """Validate or default a communicator argument (reference
    communication.py:1881)."""
    if comm is None:
        return get_comm()
    if isinstance(comm, MeshCommunication):
        return comm
    raise TypeError(f"Unknown communication, must be MeshCommunication, got {comm!r}")
