"""2-level (node × local) mesh topology + tiered collective lowerings
(ISSUE 15 tentpole).

Heat's DASO is the paper's answer to hierarchical interconnects — reduce
inside the node, synchronize across nodes — but until this module only
DASO knew the topology: every other collective lowered *flat*, as if
every hop cost the same. Production TPU scale is DCN + ICI with an
order-of-magnitude bandwidth gap (ROADMAP item 3), so this module makes
the 2-level factorization a first-class capability:

* :class:`Topology` — a declared ``(node, local)`` factorization of the
  flat device mesh. ``HEAT_TPU_TOPOLOGY=node×local`` (``2x4`` grammar)
  pins it; unset, :func:`detect` derives it from the host-process
  structure on real multi-host hardware and falls back to the DASO-style
  *emulated* two-node split on a single even-sized host mesh — so the
  tiered lowerings and their tests are real even when the links are not.
* **Tiered lowerings** (:func:`hier_psum`, :func:`hier_all_gather`,
  :func:`hier_reduce_scatter`, :func:`hier_all_to_all`) — the
  ``shard_map``-level programs the :class:`MeshCommunication` wrappers
  dispatch under ``HEAT_TPU_HIERARCHICAL=1``. The canonical all-reduce
  form is: in-node **reduce-scatter** (ICI, exact) → cross-node
  **all-reduce over the 1/local-sized shard** (DCN, optionally
  compressed via the ISSUE 9 machinery) → in-node **all-gather**. Every
  stage carries explicit ``axis_index_groups``, so the emitted
  replica-group structure is the ground truth for which tier a hop
  rides — the per-tier accounting the HLO auditor and the analytic cost
  model (:mod:`heat_tpu.telemetry.collectives`,
  ``hierarchical_*_cost``) reconcile byte-for-byte.
* **Per-tier precision** — the in-node tier always moves exact; the
  cross-node (DCN) tier honors ``HEAT_TPU_HIERARCHICAL_PREC`` (falling
  back to the flat ``HEAT_TPU_COLLECTIVE_PREC`` knob), so "exact inside
  the node, bf16/int8 across" is one env var.
* **Named-axes tier primitives** (:func:`node_mean_cross_sum`) — the
  same arithmetic on an explicit 2-D ``(node, local)`` mesh, consumed by
  :class:`heat_tpu.optim.DASO`: its formerly hand-rolled node-group
  send collective is now a call into this module (bit-equivalent to the
  legacy path — pinned by ``tests/test_hierarchy.py``).

Degenerate topologies (``1×N`` / ``N×1``) lower flat: a 1-level
hierarchy IS the flat ring, and emitting singleton-group collectives
would only add audit noise. ``HEAT_TPU_HIERARCHICAL=0`` (the default)
preserves the flat path verbatim — bit-for-bit, program-for-program.

Program-cache discipline: the tiered lowering is part of the traced
program, so callers caching programs built over the
:class:`MeshCommunication` wrappers must key on
:func:`cache_token` (alongside ``collective_prec.effective`` — same
contract as ISSUE 9).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from heat_tpu import _knobs as knobs

__all__ = [
    "Topology",
    "parse",
    "detect",
    "resolve",
    "active",
    "hierarchical_requested",
    "cross_mode",
    "fsdp_wire",
    "cache_token",
    "hier_psum",
    "hier_reduce_scatter",
    "hier_all_gather",
    "hier_all_to_all",
    "node_mean_cross_sum",
]

_ENV_TOPO = "HEAT_TPU_TOPOLOGY"
_ENV_HIER = "HEAT_TPU_HIERARCHICAL"
_ENV_PREC = "HEAT_TPU_HIERARCHICAL_PREC"


@dataclass(frozen=True)
class Topology:
    """A 2-level factorization of a flat ``p``-device mesh.

    ``node`` is the slow (DCN) tier size, ``local`` the fast (ICI) tier
    size; flat mesh position ``i`` sits at ``(i // local, i % local)`` —
    node-major, the layout DASO's 2-D mesh has always used. ``source``
    records where the factorization came from (``"knob"`` /
    ``"detected"`` / ``"trivial"``) for telemetry and debugging.
    """

    node: int
    local: int
    source: str = "detected"

    @property
    def size(self) -> int:
        return self.node * self.local

    @property
    def nontrivial(self) -> bool:
        """Whether tiered lowering differs from flat: both tiers > 1."""
        return self.node > 1 and self.local > 1

    def node_groups(self) -> List[List[int]]:
        """``axis_index_groups`` of the in-node (ICI) tier: one group per
        node, covering its ``local`` consecutive flat positions."""
        return [
            [n * self.local + l for l in range(self.local)]
            for n in range(self.node)
        ]

    def cross_groups(self) -> List[List[int]]:
        """``axis_index_groups`` of the cross-node (DCN) tier: one group
        per local position, striding across nodes."""
        return [
            [n * self.local + l for n in range(self.node)]
            for l in range(self.local)
        ]

    def describe(self) -> str:
        return f"{self.node}x{self.local}"


def parse(raw: str, p: int) -> Optional[Topology]:
    """Parse the ``HEAT_TPU_TOPOLOGY`` grammar (``NODExLOCAL``, ``x`` or
    ``×``) against a ``p``-device mesh. Malformed strings or
    factorizations that do not multiply to ``p`` return None (the caller
    falls back to detection) — with a warning for the mismatch case,
    which is a real configuration error, not an unset knob."""
    s = (raw or "").strip().lower().replace("×", "x")
    if not s:
        return None
    parts = s.split("x")
    if len(parts) != 2:
        return None
    try:
        node, local = int(parts[0]), int(parts[1])
    except ValueError:
        return None
    if node <= 0 or local <= 0:
        return None
    if node * local != p:
        warnings.warn(
            f"HEAT_TPU_TOPOLOGY={raw!r} declares {node}x{local}="
            f"{node * local} positions but the mesh has {p}; falling back "
            "to auto-detection"
        )
        return None
    return Topology(node, local, source="knob")


def detect(p: int) -> Topology:
    """Auto-detect a factorization of ``p`` devices.

    * Real multi-host runs: one node per host process (the DCN boundary
      XLA actually crosses), when the process count divides ``p``.
    * Single-host emulation: the DASO-style two-node split on even
      meshes — exactly how DASO's tests have always faked DCN on the
      virtual CPU mesh, so the tiered lowerings and their replica-group
      assertions exercise for real even when the links don't exist.
    * Everything else: trivial ``1×p`` (tiered lowering inactive).
    """
    nproc = jax.process_count()
    if nproc > 1 and p % nproc == 0:
        return Topology(nproc, p // nproc, source="detected")
    if p > 1 and p % 2 == 0:
        return Topology(2, p // 2, source="detected")
    return Topology(1, p, source="trivial")


def resolve(p: int) -> Topology:
    """The active topology for a ``p``-device mesh: the knob when set and
    valid, else detection."""
    topo = parse(knobs.raw(_ENV_TOPO, "") or "", p)
    return topo if topo is not None else detect(p)


def hierarchical_requested() -> bool:
    """The ``HEAT_TPU_HIERARCHICAL`` bit (default off)."""
    return bool(knobs.get(_ENV_HIER))


def active(p: int) -> Optional[Topology]:
    """The topology to lower tiered against, or None for the flat path:
    requires the ``HEAT_TPU_HIERARCHICAL`` opt-in AND a nontrivial
    factorization (degenerate ``1×N`` / ``N×1`` topologies lower flat)."""
    if not hierarchical_requested():
        return None
    topo = resolve(p)
    return topo if topo.nontrivial else None


def cross_mode(dtype, precision: Optional[str] = None) -> str:
    """The wire mode of the CROSS-NODE tier for one payload: an explicit
    per-call ``precision=`` wins; else ``HEAT_TPU_HIERARCHICAL_PREC``
    when set; else the flat ``HEAT_TPU_COLLECTIVE_PREC`` knob. Demoted to
    ``off`` for non-float payloads, like every ISSUE 9 surface."""
    from . import collective_prec

    if precision is None:
        raw = (knobs.raw(_ENV_PREC, "") or "").strip().lower()
        if raw in collective_prec.MODES:
            precision = raw
    return collective_prec.effective(dtype, precision)


def fsdp_wire(dtype, p: int, precision: Optional[str] = None) -> str:
    """The wire mode of one FSDP weight gather (and its transpose
    reduce-scatter) for one leaf (ISSUE 18, parallel/fsdp.py): an
    explicit per-rule ``precision`` wins; else ``HEAT_TPU_FSDP_PREC``
    when set; else — under an ACTIVE 2-level topology — the cross-node
    chain (:func:`cross_mode`: ``HEAT_TPU_HIERARCHICAL_PREC``, then
    ``HEAT_TPU_COLLECTIVE_PREC``), because there the in-node tier moves
    exact regardless and only the DCN hop compresses; else ``off``. The
    flat-mesh default is deliberately exact, NOT the global collective
    knob: a compressed weight gather changes the model every step, so
    lossy weight wires require the FSDP-specific opt-in. Demoted to
    ``off`` for non-float payloads like every ISSUE 9 surface."""
    from . import collective_prec

    if precision is None:
        raw = (knobs.raw("HEAT_TPU_FSDP_PREC", "") or "").strip().lower()
        if raw in collective_prec.MODES:
            precision = raw
    if precision is None:
        if active(p) is not None:
            return cross_mode(dtype, None)
        return "off"
    return collective_prec.effective(dtype, precision)


def cache_token(p: int) -> Tuple:
    """The program-cache key component that pins the tiered-lowering
    state of a traced program: ``(hierarchical?, node, local,
    cross-tier knob)``. Callers caching programs built over the
    MeshCommunication wrappers include this alongside
    ``collective_prec.effective(dtype)`` — flipping
    ``HEAT_TPU_HIERARCHICAL`` (or re-declaring the topology) must key a
    different compiled program, never silently reuse a stale one."""
    topo = active(p)
    if topo is None:
        return ("flat",)
    return (
        "hier", topo.node, topo.local,
        (knobs.raw(_ENV_PREC, "") or "").strip().lower(),
    )


# -- tiered lowerings over a FLAT mesh axis -----------------------------------
# These run inside shard_map kernels (or GSPMD bodies via shard_map) over
# the communicator's single flat axis; the tier structure enters purely
# through axis_index_groups, which is what the emitted replica groups —
# and hence the per-tier HLO audit — reflect.


def _pad_flat(x, multiple: int):
    """(flat payload zero-padded to a multiple, original element count)."""
    n = x.size
    chunk = -(-n // multiple)
    n_pad = chunk * multiple
    flat = x.reshape(-1)
    if n_pad != n:
        flat = jnp.pad(flat, (0, n_pad - n))
    return flat, n


def hier_psum(x, axis_name: str, topo: Topology,
              cross_wire: str = "off", block: Optional[int] = None):
    """Tiered all-reduce: in-node reduce-scatter (exact) → cross-node
    all-reduce of the ``1/local`` shard (``cross_wire``-compressed) →
    in-node all-gather. Bit-parity with the flat ``lax.psum`` holds
    whenever the payload's sums are exactly representable (integer
    payloads, integer-valued floats); general float payloads differ only
    by summation association."""
    from . import collective_prec

    flat, n = _pad_flat(x, topo.local)
    s = jax.lax.psum_scatter(
        flat, axis_name, scatter_dimension=0,
        axis_index_groups=topo.node_groups(), tiled=True,
    )
    s = collective_prec.psum(
        s, axis_name, topo.node, cross_wire, block,
        groups=topo.cross_groups(),
    )
    out = jax.lax.all_gather(
        s, axis_name, axis_index_groups=topo.node_groups(), tiled=True,
    )
    return out[:n].reshape(x.shape)


def hier_reduce_scatter(x, axis_name: str, topo: Topology,
                        cross_wire: str = "off",
                        block: Optional[int] = None):
    """Tiered reduce-scatter to the global ``1/p`` chunk: in-node
    reduce-scatter (exact) to the ``1/local`` shard, then cross-node
    reduce-scatter of that shard (``cross_wire``-compressed). Returns the
    1-D ``(ceil(numel/p),)`` chunk owned by this position — the same
    contract as the flat ``MeshCommunication.reduce_scatter``."""
    from . import collective_prec

    p = topo.size
    flat, _ = _pad_flat(x, p)
    c = flat.size // p
    # chunk transpose: stage 1 hands local-position l the l-th quarter,
    # stage 2 hands node-position n the n-th piece of it — so to land the
    # FLAT chunk n·local+l on device (n, l) (the contract the tiered
    # all-gather reassembles), chunks are pre-arranged (local, node)-major
    arranged = flat.reshape(topo.node, topo.local, c).swapaxes(0, 1)
    s = jax.lax.psum_scatter(
        arranged.reshape(-1), axis_name, scatter_dimension=0,
        axis_index_groups=topo.node_groups(), tiled=True,
    )
    return collective_prec.reduce_scatter(
        s, axis_name, topo.node, cross_wire, block,
        groups=topo.cross_groups(),
    )


def _two_stage_gather(axis_name: str, topo: Topology):
    """The exact two-stage gather mover: cross-node first (DCN), then
    in-node (ICI), reordered to the flat gather's node-major source
    order. Returns a function u -> (p,) + u.shape stacked blocks."""

    def mover(u):
        g1 = jax.lax.all_gather(
            u, axis_name, axis_index_groups=topo.cross_groups()
        )                                            # (node,) + u.shape
        g2 = jax.lax.all_gather(
            g1, axis_name, axis_index_groups=topo.node_groups()
        )                                            # (local, node) + u.shape
        g = jnp.swapaxes(g2, 0, 1)                   # (node, local) + u.shape
        return g.reshape((topo.size,) + u.shape)

    return mover


def hier_all_gather(x, axis_name: str, topo: Topology,
                    cross_wire: str = "off", block: Optional[int] = None,
                    tiled: bool = True):
    """Tiered all-gather: cross-node gather of the shard (DCN), then the
    in-node gather of the stacked node blocks (ICI). Exact mode is
    bit-identical to the flat tiled/stacked ``lax.all_gather`` — pure
    data movement, reordered to the same source-major layout. Compressed
    modes quantize ONCE at the source and move payload + scales through
    both stages (one quantization step of error, the flat compressed
    bound)."""
    from . import collective_prec as cp

    mover = _two_stage_gather(axis_name, topo)
    p = topo.size
    if cross_wire == "off" or not cp.compressible(x.dtype):
        g = mover(x)
    elif cross_wire == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        u = jax.lax.bitcast_convert_type(w, jnp.uint16)
        g = jax.lax.bitcast_convert_type(mover(u), jnp.bfloat16).astype(
            x.dtype
        )
    elif cross_wire == "int8":
        q, s = cp._quant_tensor(x)
        qg = mover(q)                                  # (p,) + x.shape
        sg = jax.lax.bitcast_convert_type(
            mover(jax.lax.bitcast_convert_type(s, jnp.uint16)), jnp.bfloat16
        )                                              # (p,)
        g = cp._deq(qg, sg.reshape((p,) + (1,) * x.ndim)).astype(x.dtype)
    else:
        block = block or cp.block_size()
        q, s = cp._quant_flat_blocks(x, block)
        qg = mover(q)                                  # (p, nb, blk)
        sg = jax.lax.bitcast_convert_type(
            mover(jax.lax.bitcast_convert_type(s, jnp.uint16)), jnp.bfloat16
        )                                              # (p, nb)
        g = cp._deq(qg, sg[..., None]).reshape(p, -1)[:, : x.size]
        g = g.reshape((p,) + x.shape).astype(x.dtype)
    if tiled and x.ndim >= 1:
        return g.reshape((p * x.shape[0],) + x.shape[1:])
    return g


def _two_stage_a2a(axis_name: str, topo: Topology):
    """The exact two-stage slab exchange: stage A swaps
    destination-local slabs inside each node (ICI), stage B swaps
    destination-node bundles across nodes (DCN). Input: an array whose
    LEADING axis is the ``p`` destination slabs (node-major); output:
    the same shape with the leading axis holding the ``p`` SOURCE slabs
    (node-major) — exactly the flat ``all_to_all(split_axis=0,
    concat_axis=0)`` contract."""

    def mover(slabs):
        b = slabs.reshape((topo.node, topo.local) + slabs.shape[1:])
        a = jax.lax.all_to_all(
            b, axis_name, split_axis=1, concat_axis=0,
            axis_index_groups=topo.node_groups(),
        )                                   # (src_local, node, ...)
        c = jax.lax.all_to_all(
            a, axis_name, split_axis=1, concat_axis=0,
            axis_index_groups=topo.cross_groups(),
        )                                   # (src_node, src_local, ...)
        return c.reshape(slabs.shape)

    return mover


def hier_all_to_all(x, axis_name: str, topo: Topology,
                    split_axis: int, concat_axis: int,
                    cross_wire: str = "off", block: Optional[int] = None):
    """Tiered (tiled) all-to-all. Exact mode is bit-identical to the
    flat ``lax.all_to_all(tiled=True)`` — both stages are pure data
    movement and the staging restores the flat source-major layout.
    Compressed modes quantize per final-destination slab at the source
    (the :func:`heat_tpu.core.collective_prec.all_to_all` slab scheme)
    and move payload + scales through both stages."""
    from . import collective_prec as cp

    p = topo.size
    mover = _two_stage_a2a(axis_name, topo)
    if cross_wire == "off" or not cp.compressible(x.dtype):
        xm = jnp.moveaxis(x, split_axis, 0)
        s = xm.shape[0]
        slabs = xm.reshape((p, s // p) + xm.shape[1:])
        out = mover(slabs)
        out = out.reshape((p, s // p) + xm.shape[1:])
        out = jnp.moveaxis(out, 1, 1 + split_axis)
        out = jnp.moveaxis(out, 0, concat_axis)
        shp = list(out.shape)
        shp[concat_axis : concat_axis + 2] = [
            shp[concat_axis] * shp[concat_axis + 1]
        ]
        return out.reshape(shp)
    if cross_wire == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        u = jax.lax.bitcast_convert_type(w, jnp.uint16)
        moved = hier_all_to_all(
            u, axis_name, topo, split_axis, concat_axis, "off", block
        )
        return jax.lax.bitcast_convert_type(moved, jnp.bfloat16).astype(
            x.dtype
        )
    # int8 / blockwise: per-destination-slab quantization, staged movement
    block = block or cp.block_size()
    w = x.shape[split_axis] // p
    xm = jnp.moveaxis(x, split_axis, 0)
    rest = xm.shape[1:]
    m = w
    for d in rest:
        m *= d
    slabs = xm.reshape(p, m)
    if cross_wire == "int8":
        nb, seg = 1, m
    else:
        seg = max(1, min(block, m))
        nb = max(1, -(-m // seg))
        if nb * seg != m:
            slabs = jnp.pad(slabs, ((0, 0), (0, nb * seg - m)))
    b3 = slabs.reshape(p, nb, seg).astype(jnp.float32)
    s = cp._scale_of(jnp.max(jnp.abs(b3), axis=2))           # (p, nb)
    q = jnp.clip(
        jnp.round(b3 / s.astype(jnp.float32)[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    qt = mover(q)                                            # (p, nb, seg)
    st = jax.lax.bitcast_convert_type(
        mover(jax.lax.bitcast_convert_type(s, jnp.uint16)), jnp.bfloat16
    )                                                        # (p, nb)
    deq = cp._deq(qt, st[..., None]).reshape(p, -1)[:, :m]
    deq = deq.reshape((p, w) + rest)
    deq = jnp.moveaxis(deq, 1, 1 + split_axis)
    deq = jnp.moveaxis(deq, 0, concat_axis)
    shp = list(deq.shape)
    shp[concat_axis : concat_axis + 2] = [
        shp[concat_axis] * shp[concat_axis + 1]
    ]
    return deq.reshape(shp).astype(x.dtype)


# -- named-axes tier primitives (the DASO form) --------------------------------


def node_mean_cross_sum(x, *, local_axis: str, node_axis: str, n_node: int,
                        wire: str, cast_dtype=jnp.bfloat16,
                        block: Optional[int] = None):
    """DASO's send primitive on an explicit 2-D ``(node, local)`` mesh:
    the node representative is the MEAN over the fast (ICI) tier, then a
    reduced-precision SUM across the slow (DCN) tier — the raw sum, not
    the average: DASO folds ``n_nodes`` into its staleness-weighted
    merge denominator (reference dp_optimizer.py:502-556).

    ``wire`` semantics match the DASO contract exactly (the
    bit-equivalence oracle in tests/test_hierarchy.py pins this against
    the legacy hand-rolled kernel): ``off`` moves ``cast_dtype`` on the
    wire (the historic bf16 downcast), ``bf16`` is that same program
    with the dtype pinned, ``int8``/``blockwise`` run the EQuARX
    two-phase quantized node psum and return an f32-accurate payload."""
    from . import collective_prec

    rep = jax.lax.pmean(x, local_axis)
    if wire in ("int8", "blockwise") and collective_prec.compressible(
        x.dtype
    ):
        return collective_prec.psum(rep, node_axis, n_node, wire, block)
    wire_cast = jnp.bfloat16 if wire == "bf16" else cast_dtype
    return jax.lax.psum(rep.astype(wire_cast), node_axis)
