"""Array construction routines.

Re-design of reference heat/core/factories.py:40-1323. The reference builds
the full array on every rank and slices out the local chunk
(factories.py:381-384), or stitches pre-distributed local shards together via
a neighbor handshake (``is_split``, factories.py:386-429). Here construction
is one `device_put` with a `NamedSharding` (single-controller), and the
``is_split`` path maps onto assembling a global array from per-position
blocks.
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Sequence, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from . import program_cache, types
from .communication import MeshCommunication, sanitize_comm
from .devices import Device, sanitize_device
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis, sanitize_shape

__all__ = [
    "arange",
    "array",
    "asarray",
    "empty",
    "empty_like",
    "eye",
    "full",
    "full_like",
    "linspace",
    "logspace",
    "meshgrid",
    "ones",
    "ones_like",
    "zeros",
    "zeros_like",
]


def _wrap(
    data: jax.Array,
    split: Optional[int],
    device: Device,
    comm: MeshCommunication,
    dtype: Optional[Type[types.datatype]] = None,
) -> DNDarray:
    return DNDarray.from_logical(data, split, device, comm, dtype)


def arange(*args, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """Evenly spaced values in [start, stop) with step (reference
    factories.py:40)."""
    num_of_param = len(args)
    if num_of_param == 1:
        start, stop, step = 0, args[0], 1
    elif num_of_param == 2:
        start, stop, step = args[0], args[1], 1
    elif num_of_param == 3:
        start, stop, step = args
    else:
        raise TypeError(f"function takes minimum one and at most 3 positional arguments ({num_of_param} given)")

    if dtype is None:
        # numpy semantics: all-int args give the platform int, else float32
        if all(isinstance(a, int) for a in (start, stop, step)):
            dtype = types.int64
        else:
            dtype = types.float32
    dtype = types.canonical_heat_type(dtype)
    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    data = jnp.arange(start, stop, step, dtype=dtype.jnp_type())
    return _wrap(data, sanitize_axis(data.shape, split), device, comm, dtype)


def array(
    obj: Any,
    dtype: Optional[Type[types.datatype]] = None,
    copy: Optional[bool] = True,
    ndmin: int = 0,
    order: str = "C",
    split: Optional[int] = None,
    is_split: Optional[int] = None,
    device: Optional[Union[str, Device]] = None,
    comm: Optional[MeshCommunication] = None,
) -> DNDarray:
    """The main constructor (reference factories.py:150).

    ``split`` distributes the given *global* data along an axis; ``is_split``
    declares ``obj`` to be this process's *local* shard of a distributed
    array (the reference infers the global shape via a neighbor handshake,
    factories.py:386-429; under a single controller every position holds the
    same block list, so the global shape is locally computable).
    """
    with telemetry.span("heat_tpu.array.prepare"):
        return _array(obj, dtype, copy, ndmin, order, split, is_split, device, comm)


def _array(obj, dtype, copy, ndmin, order, split, is_split, device, comm) -> DNDarray:
    """:func:`array` under its span: host data to a placed, laid-out DNDarray."""
    if split is not None and is_split is not None:
        raise ValueError(f"split and is_split are mutually exclusive parameters")
    device = sanitize_device(device)
    comm = sanitize_comm(comm)

    if isinstance(obj, DNDarray):
        if dtype is None and split is None and is_split is None:
            if copy:
                # a real buffer copy, not an aliasing wrapper: the source
                # may later be resplit_ in place, which DONATES its buffer
                # (core/program_cache.py) — an aliased "copy" would die
                # with it on backends that honor the donation
                return DNDarray(
                    jnp.copy(obj.larray), obj.shape, obj.dtype, obj.split,
                    device, comm, True,
                )
            return obj
        import jax as _jax

        if obj.split is not None and _jax.process_count() > 1:
            data = obj._replicated()  # compiled relayout; _wrap re-shards
        else:
            data = obj._logical()
        if dtype is not None:
            data = data.astype(types.canonical_heat_type(dtype).jnp_type())
        tgt_split = split if split is not None else (obj.split if is_split is None else is_split)
        return _wrap(data, tgt_split, device, comm)

    if isinstance(obj, (jnp.ndarray,)):
        data = obj
    else:
        data = np.asarray(obj, order=order)

    if dtype is not None:
        dtype = types.canonical_heat_type(dtype)
        data = jnp.asarray(data, dtype=dtype.jnp_type())
    else:
        if isinstance(data, np.ndarray) and data.dtype == np.float64 and not isinstance(obj, np.ndarray):
            # python floats default to float32 (reference types promotion)
            data = jnp.asarray(data, dtype=jnp.float32)
        else:
            data = jnp.asarray(data)
        dtype = types.canonical_heat_type(data.dtype)

    while data.ndim < ndmin:
        data = data[None]

    if is_split is not None:
        # reference semantics: the given array is this *process's* local
        # shard and the global shape is inferred from all processes
        # (factories.py:386-429, neighbor handshake). Single-controller JAX
        # has one process, so the local portion IS the global array;
        # multi-host assembles the shards via
        # jax.make_array_from_process_local_data (SURVEY §7 stage 1).
        is_split = sanitize_axis(data.shape, is_split)
        if jax.process_count() > 1:
            return _assemble_is_split(data, is_split, device, comm, dtype)
        return _wrap(data, is_split, device, comm, dtype)

    split = sanitize_axis(data.shape, split)
    return _wrap(data, split, device, comm, dtype)


def _assemble_ragged(
    local,
    split: int,
    gshape,
    all_shapes,
    device,
    comm,
    dtype,
) -> "DNDarray":
    """Assemble arbitrary ragged per-process blocks into the canonical
    layout. Stage 1: every process pads its block into a uniform slot of
    ``c_stage = max_p ceil(len_p / ldc_p)`` rows per device, so the staged
    array is canonically sharded by construction. Stage 2: one compiled
    gather maps canonical positions to staged positions (the index map is
    host-computable from the allgathered lengths) and lands with the
    result's sharding."""
    import jax

    lens = all_shapes[:, split].astype(np.int64)
    n = int(lens.sum())
    nprocs = jax.process_count()
    # per-process device counts, in process order
    ldc = np.zeros((nprocs,), dtype=np.int64)
    for dev in comm.devices:
        ldc[dev.process_index] += 1
    if (ldc == 0).any():
        raise NotImplementedError(
            "ragged is_split needs every process to own mesh devices"
        )
    c_stage = int(max(-(-int(l) // int(d)) for l, d in zip(lens, ldc)))
    c_stage = max(c_stage, 1)
    slot = ldc * c_stage  # rows per process in the staging layout
    n_stage = int(slot.sum())  # == c_stage * comm.size

    ht_dtype = (
        types.canonical_heat_type(dtype)
        if dtype is not None
        else types.canonical_heat_type(local.dtype)
    )
    block = np.asarray(local).astype(ht_dtype.jnp_type())
    pidx = jax.process_index()
    padw = [(0, 0)] * block.ndim
    padw[split] = (0, int(slot[pidx]) - block.shape[split])
    block = np.pad(block, padw)
    stage_shape = gshape[:split] + (n_stage,) + gshape[split + 1 :]
    staged = jax.make_array_from_process_local_data(
        comm.sharding(split, len(gshape)), block, stage_shape
    )

    # canonical position j < n reads staged position slot_start[q] + (j -
    # prefix[q]) where q owns global row j; pads read row 0
    prefix = np.concatenate([[0], np.cumsum(lens)])
    slot_start = np.concatenate([[0], np.cumsum(slot)])
    n_pad = comm.padded_size(n)
    j = np.arange(n_pad, dtype=np.int64)
    q = np.searchsorted(prefix, np.minimum(j, n - 1), side="right") - 1
    src = np.where(j < n, slot_start[q] + (j - prefix[q]), 0)
    idx = jnp.asarray(src)

    # one cached compiled re-chunk gather: the index map is data (an
    # argument), so repeated is_split assemblies over the same (split,
    # rank) layout reuse one program even when the per-process lengths —
    # and hence the map's values — differ
    gather = program_cache.cached_program(
        "is_split_gather", (split, len(gshape)),
        lambda: (lambda b, ix: jnp.take(b, ix, axis=split)),
        comm=comm, out_shardings=comm.sharding(split, len(gshape)),
    )
    buf = gather(staged, idx)
    return DNDarray(buf, gshape, ht_dtype, split, device, comm, True)


def _assemble_is_split(
    data,
    split: int,
    device: Device,
    comm: MeshCommunication,
    dtype: Optional[Type[types.datatype]],
) -> DNDarray:
    """Assemble a global DNDarray from per-controller-process local shards
    (the reference's ``is_split`` neighbor handshake, factories.py:386-429).

    Every process calls this with *its* block along ``split``; blocks are
    ordered by process index. The global extent is inferred by all-gathering
    the local shapes (the handshake analog); non-split dims must agree.

    Blocks matching the canonical ceil-rule chunks (the layout produced by
    per-host sharded data loading) assemble directly; arbitrary RAGGED
    extents go through :func:`_assemble_ragged` — a staging layout plus one
    compiled re-chunk gather (the branch is decided collectively from the
    allgathered shapes).
    """
    from jax.experimental import multihost_utils

    local = np.asarray(data)
    pidx = jax.process_index()
    # handshake: gather (shape..., dtype code) from every process in one go
    meta = np.asarray(list(local.shape) + [np.dtype(local.dtype).num], dtype=np.int64)
    all_meta = np.asarray(multihost_utils.process_allgather(meta)).reshape(
        jax.process_count(), local.ndim + 1
    )
    all_shapes = all_meta[:, :-1]
    for d in range(local.ndim):
        if d != split and len(set(all_shapes[:, d].tolist())) != 1:
            raise ValueError(
                f"is_split: non-split dimension {d} differs across processes: "
                f"{sorted(set(all_shapes[:, d].tolist()))}"
            )
    if dtype is None and len(set(all_meta[:, -1].tolist())) != 1:
        raise ValueError(
            "is_split: local shard dtypes differ across processes "
            f"(numpy dtype codes {sorted(set(all_meta[:, -1].tolist()))}); "
            "pass dtype= explicitly"
        )
    n = int(all_shapes[:, split].sum())
    gshape = tuple(local.shape[:split]) + (n,) + tuple(local.shape[split + 1 :])

    c = comm.chunk_size(n)
    mesh_positions = [
        i for i, dev in enumerate(comm.devices) if dev.process_index == pidx
    ]
    if not mesh_positions or mesh_positions != list(
        range(mesh_positions[0], mesh_positions[0] + len(mesh_positions))
    ):
        raise NotImplementedError(
            "is_split requires this process's devices to be contiguous in the "
            "communicator mesh"
        )
    first, count = mesh_positions[0], len(mesh_positions)
    # canonical-vs-ragged is decided COLLECTIVELY from the allgathered
    # shapes — every process computes every process's (have, want) spans and
    # agrees on the branch, because the two branches issue different
    # collective programs (a per-process decision could deadlock the job)
    lens_all = all_shapes[:, split].astype(np.int64)
    prefixes = np.concatenate([[0], np.cumsum(lens_all)])
    nprocs = jax.process_count()
    first_all = np.full((nprocs,), -1, dtype=np.int64)
    ldc_all = np.zeros((nprocs,), dtype=np.int64)
    for i, dev in enumerate(comm.devices):
        if first_all[dev.process_index] < 0:
            first_all[dev.process_index] = i
        ldc_all[dev.process_index] += 1
    canonical = True
    for p_i in range(nprocs):
        w_lo = min(int(first_all[p_i]) * c, n)
        w_hi = min((int(first_all[p_i]) + int(ldc_all[p_i])) * c, n)
        if (int(prefixes[p_i]), int(prefixes[p_i + 1])) != (w_lo, w_hi):
            canonical = False
            break
    if not canonical:
        # RAGGED blocks (the reference accepts any per-rank extents,
        # factories.py:386-429): stage the blocks in a uniform-slot layout,
        # then one compiled index-map gather re-chunks to canonical — the
        # DCN all-to-all the relayout requires, emitted by XLA
        return _assemble_ragged(
            local, split, gshape, all_shapes, device, comm, dtype
        )
    phys_rows = count * c
    if local.shape[split] < phys_rows:
        padw = [(0, 0)] * local.ndim
        padw[split] = (0, phys_rows - local.shape[split])
        local = np.pad(local, padw)

    ht_dtype = (
        types.canonical_heat_type(dtype)
        if dtype is not None
        else types.canonical_heat_type(local.dtype)
    )
    local = local.astype(ht_dtype.jnp_type())
    pshape = comm.padded_shape(gshape, split)
    arr = jax.make_array_from_process_local_data(
        comm.sharding(split, len(gshape)), local, pshape
    )
    return DNDarray(arr, gshape, ht_dtype, split, device, comm, True)


def asarray(obj, dtype=None, copy=None, order="C", is_split=None, device=None, comm=None) -> DNDarray:
    """Convert to DNDarray without copying when possible (reference
    factories.py: `asarray`)."""
    if isinstance(obj, DNDarray) and dtype is None and is_split is None and device is None:
        return obj
    return array(obj, dtype=dtype, copy=copy, is_split=is_split, device=device, comm=comm)


def __factory(shape, dtype, split, fill, device, comm, order="C") -> DNDarray:
    shape = sanitize_shape(shape)
    dtype = types.canonical_heat_type(dtype)
    split = sanitize_axis(shape, split)
    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    data = fill(shape, dtype=dtype.jnp_type())
    return _wrap(data, split, device, comm, dtype)


def empty(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Uninitialized (zero-filled on XLA) array (reference factories.py:513)."""
    return __factory(shape, dtype, split, jnp.zeros, device, comm, order)


def full(shape, fill_value, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Constant-filled array (reference factories.py:722)."""

    def filler(s, dtype):
        return jnp.full(s, fill_value, dtype=dtype)

    return __factory(shape, dtype, split, filler, device, comm, order)


def ones(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory(shape, dtype, split, jnp.ones, device, comm, order)


def zeros(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory(shape, dtype, split, jnp.zeros, device, comm, order)


def __factory_like(a, dtype, split, factory, device, comm, order="C", **kwargs) -> DNDarray:
    shape = a.shape if isinstance(a, DNDarray) else np.asarray(a).shape
    if dtype is None:
        dtype = a.dtype if isinstance(a, DNDarray) else types.canonical_heat_type(np.asarray(a).dtype)
    if split is None:
        split = a.split if isinstance(a, DNDarray) else None
    if device is None and isinstance(a, DNDarray):
        device = a.device
    if comm is None and isinstance(a, DNDarray):
        comm = a.comm
    return factory(shape, dtype=dtype, split=split, device=device, comm=comm, order=order, **kwargs)


def empty_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, empty, device, comm, order)


def full_like(a, fill_value, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, full, device, comm, order, fill_value=fill_value)


def ones_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, ones, device, comm, order)


def zeros_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    return __factory_like(a, dtype, split, zeros, device, comm, order)


def eye(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """2-D identity-like array (reference factories.py:589)."""
    if isinstance(shape, (int, np.integer)):
        gshape = (int(shape), int(shape))
    else:
        shape = tuple(shape)
        gshape = (int(shape[0]), int(shape[1] if len(shape) > 1 else shape[0]))
    dtype = types.canonical_heat_type(dtype)
    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    data = jnp.eye(gshape[0], gshape[1], dtype=dtype.jnp_type())
    return _wrap(data, sanitize_axis(gshape, split), device, comm, dtype)


def linspace(
    start,
    stop,
    num: int = 50,
    endpoint: bool = True,
    retstep: bool = False,
    dtype=None,
    split=None,
    device=None,
    comm=None,
):
    """num evenly spaced samples over [start, stop] (reference
    factories.py:899)."""
    num = int(num)
    if num <= 0:
        raise ValueError(f"number of samples 'num' must be non-negative integer, but was {num}")
    start = float(start)
    stop = float(stop)
    step = (stop - start) / max(1, (num - 1 if endpoint else num))
    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    data = jnp.linspace(start, stop, num, endpoint=endpoint, dtype=None)
    if dtype is not None:
        data = data.astype(types.canonical_heat_type(dtype).jnp_type())
    elif data.dtype == jnp.float64:
        data = data.astype(jnp.float32)
    ht = _wrap(data, sanitize_axis(data.shape, split), device, comm)
    if retstep:
        return ht, step
    return ht


def logspace(
    start, stop, num=50, endpoint=True, base=10.0, dtype=None, split=None, device=None, comm=None
) -> DNDarray:
    """num samples on a log scale (reference factories.py:985)."""
    y = linspace(start, stop, num=num, endpoint=endpoint, split=split, device=device, comm=comm)
    from . import arithmetics

    result = arithmetics.pow(float(base), y)
    if dtype is None:
        return result
    return result.astype(types.canonical_heat_type(dtype))


def meshgrid(*arrays, indexing: str = "xy") -> List[DNDarray]:
    """Coordinate matrices from 1-D coordinate vectors (reference
    factories.py:1048). Distributed: if any input is split, the first two
    output grids are split consistently along their major dims."""
    if indexing not in ("xy", "ij"):
        raise ValueError(f"indexing must be 'xy' or 'ij', got {indexing}")
    if len(arrays) == 0:
        return []
    hts = [a if isinstance(a, DNDarray) else array(a) for a in arrays]
    split_in = [a.split for a in hts]
    if sum(s is not None for s in split_in) > 1:
        raise ValueError("split axis can be defined for at most one input")
    comm = hts[0].comm
    device = hts[0].device
    logs = [a._logical() for a in hts]
    outs = jnp.meshgrid(*logs, indexing=indexing)
    # output split: if input i was split, every output is split along the dim
    # that carries input i's coordinate
    out_split = None
    which = next((i for i, s in enumerate(split_in) if s is not None), None)
    if which is not None:
        if indexing == "xy" and which in (0, 1) and len(hts) > 1:
            out_split = 1 - which if which < 2 else which
        else:
            out_split = which
    return [DNDarray.from_logical(o, out_split, device, comm) for o in outs]
