"""Quantized & compressed collective payloads (ISSUE 9 tentpole).

Heat's design splits every op into local compute plus explicit
collectives, so at scale the wire is the bottleneck. EQuARX
(arXiv:2506.17615) shows block-wise quantized all-reduce inside XLA
winning ~2x for small/medium tensors, and cross-replica weight-update
sharding (arXiv:2004.13336) shows the gradient path tolerates
reduced-precision aggregation when done carefully. This module
generalizes the one ad-hoc instance the repo already shipped — DASO's
bf16 cross-node parameter average — into a first-class, knob-controlled,
HLO-audited collective-precision layer:

* ``HEAT_TPU_COLLECTIVE_PREC=off|bf16|int8|blockwise`` (default ``off``)
  plus a per-call ``precision=`` override on every instrumented surface
  (:meth:`MeshCommunication.psum` & friends, ``manipulations.resplit``,
  ``DataParallel.make_train_step``, ``DASO``).
* ``bf16`` — cast → collective → upcast in the same trace. 2x wire
  reduction for f32 payloads (4x for f64), ~3 decimal digits kept.
* ``int8`` — EQuARX per-tensor scheme: one max-abs scale, symmetric
  round-to-nearest onto [-127, 127], the collective moves int8 + the
  bf16 scale, dequantize on the far side. ~4x wire reduction for f32.
* ``blockwise`` — the same scheme with one scale per block
  (``HEAT_TPU_COLLECTIVE_PREC_BLOCK`` elements, default 128), so a
  single outlier only poisons its own block's resolution. ~3.9x wire
  reduction for f32 at the default block.

Two execution contexts, same arithmetic:

* **shard_map kernels** (the :class:`MeshCommunication` wrapper family):
  per-shard payloads are quantized locally (no extra collective — the
  max-abs runs on the local block) and the scale rides the same
  collective as the payload. A quantized ``psum`` is the EQuARX
  two-phase form: quantize → all-to-all (the reduce-scatter phase) →
  dequantize + accumulate → requantize → all-gather → dequantize, i.e.
  ``2·B/4·(p-1)`` wire bytes instead of the f32 ring all-reduce's
  ``2·B·(p-1)``.
* **GSPMD programs** (the relayout family): quantize, pin the *wire*
  tensor's layout with ``with_sharding_constraint`` so the emitted
  collective moves the compressed dtype, dequantize after. Per-tensor
  scales cost one scalar cross-shard max all-reduce; blockwise scales
  (blocked along the last axis, which stays shard-local) are replicated
  by one small all-gather.

Every compressed program is ground-truthed: the analytic cost model
(:mod:`heat_tpu.telemetry.collectives`) takes a ``precision=`` argument
and the HLO auditor verifies the compiled program's emitted collectives
move the predicted *smaller* dtype/byte volume (drift fails CI).

Accuracy contract (pinned by ``tests/test_collective_prec.py``):

* ``off`` — bit-identical to the pre-knob programs (the default);
* ``bf16`` — per-element error bounded by bf16 rounding of the payload
  (~2^-8 relative);
* ``int8``/``blockwise`` — per-element error bounded relative to the
  max-abs of the scale group: one quantization step is at most
  ``amax/254``; a two-phase psum over ``p`` shards accumulates at most
  ``(p+1)`` steps. Integer/bool payloads always pass through exact;
  non-finite payloads (inf/nan) are outside the contract.

Only lossy-tolerant data movement honors the global knob: exactness-
critical sites (sort/unique index circulation, histogram counts, the QR
rings) pin ``precision="off"`` at the call site.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from heat_tpu import _knobs as knobs

from ..telemetry import collectives as _cost

__all__ = [
    "MODES",
    "DEFAULT_BLOCK",
    "mode",
    "block_size",
    "resolve",
    "effective",
    "compressible",
    "blockwise_axis_ok",
    "psum",
    "pmean",
    "reduce_scatter",
    "all_gather",
    "ppermute",
    "all_to_all",
    "gspmd_reshard",
    "local_roundtrip",
    "quant_error_bound",
    "allreduce_wire_dtype",
    "bench_field",
]

MODES = ("off", "bf16", "int8", "blockwise")
_ENV_MODE = "HEAT_TPU_COLLECTIVE_PREC"
_ENV_BLOCK = "HEAT_TPU_COLLECTIVE_PREC_BLOCK"

# One scale per this many payload elements in blockwise mode. 128 keeps the
# bf16 scale overhead at 1/64 of the int8 payload (~1.6%) while localizing
# outliers; the cost model (telemetry/collectives.py DEFAULT_WIRE_BLOCK)
# carries the same default so predictions and programs agree.
DEFAULT_BLOCK = _cost.DEFAULT_WIRE_BLOCK


def mode() -> str:
    """The active ``HEAT_TPU_COLLECTIVE_PREC`` value (malformed -> off)."""
    raw = (knobs.raw(_ENV_MODE, "") or "").strip().lower()
    return raw if raw in MODES else "off"


def block_size() -> int:
    """Blockwise scale granularity (``HEAT_TPU_COLLECTIVE_PREC_BLOCK``,
    default :data:`DEFAULT_BLOCK`; malformed or non-positive -> default)."""
    raw = (knobs.raw(_ENV_BLOCK, "") or "").strip()
    if raw:
        try:
            n = int(raw)
            if n > 0:
                return n
        except ValueError:
            pass
    return DEFAULT_BLOCK


def resolve(precision: Optional[str] = None) -> str:
    """Per-call override semantics: an explicit ``precision=`` wins over
    the env knob; ``None`` consults :func:`mode`. Unknown values raise —
    a typo'd mode must never silently run exact (or lossy)."""
    if precision is None:
        return mode()
    p = str(precision).strip().lower()
    if p not in MODES:
        raise ValueError(
            f"precision must be one of {MODES}, got {precision!r}"
        )
    return p


def compressible(dtype) -> bool:
    """Only floating payloads are lossy-compressible; integer/bool/complex
    payloads (indices, counts, sort keys) always move exact."""
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def effective(dtype, precision: Optional[str] = None) -> str:
    """The wire mode one payload actually gets: the resolved mode, demoted
    to ``off`` for non-float dtypes. This is the value program-cache keys
    must carry — it fully determines the traced program."""
    m = resolve(precision)
    if m == "off" or not compressible(dtype):
        return "off"
    return m


def blockwise_axis_ok(shape: Sequence[int], split: Optional[int]) -> bool:
    """Whether the GSPMD blockwise layout applies: blocks run along the
    last axis, which must be a real axis distinct from the sharded one so
    every block is shard-local (its max-abs needs no collective)."""
    return len(shape) >= 2 and split != len(shape) - 1 and int(shape[-1]) > 0


def blockwise_segments(extent: int, block: int) -> Tuple[int, int]:
    """(n_blocks, segment) decomposition of a last-axis ``extent`` for the
    GSPMD path: even ``block``-sized segments when they divide the axis,
    else one whole-row segment (no wire-wasting pad). The cost model
    mirrors this rule exactly."""
    extent = int(extent)
    if extent >= block and extent % block == 0:
        return extent // block, block
    return 1, extent


# -- quantization arithmetic (pure jnp; runs inside any trace) ----------------


def _scale_of(amax):
    """Zero-safe symmetric scale: q = round(x/scale) targets [-127, 127];
    an all-zero group quantizes through scale 1 (payload stays zero).
    The scale ships in **bf16** — half the scale wire traffic of f32,
    and since quantization divides by the bf16-rounded value the
    roundtrip error stays one quantization step (the extra ~2^-8 scale
    rounding only rescales the step, it does not compound)."""
    s = jnp.where(amax > 0, amax / 127.0, jnp.ones_like(amax))
    return s.astype(jnp.bfloat16)


def _quant_tensor(x) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor int8 quantization: (q int8, scale bf16 scalar)."""
    xf = x.astype(jnp.float32)
    s = _scale_of(jnp.max(jnp.abs(xf)))
    q = jnp.clip(jnp.round(xf / s.astype(jnp.float32)), -127.0, 127.0)
    return q.astype(jnp.int8), s


def _quant_flat_blocks(x, block: int) -> Tuple[jax.Array, jax.Array]:
    """Flat blockwise quantization: the payload raveled and zero-padded to
    ``nblk * block``; returns (q int8 (nblk, block), scales bf16 (nblk,))."""
    n = x.size
    block = max(1, min(block, n))  # a payload smaller than one block
    # must not be zero-padded up to it (16x wire blowup for tiny tensors)
    nblk = max(1, -(-n // block))
    flat = jnp.ravel(x).astype(jnp.float32)
    if nblk * block != n:
        flat = jnp.pad(flat, (0, nblk * block - n))
    b = flat.reshape(nblk, block)
    s = _scale_of(jnp.max(jnp.abs(b), axis=1))
    q = jnp.clip(
        jnp.round(b / s.astype(jnp.float32)[:, None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, s


def _deq(q, s):
    """int8 payload × bf16 scale in f32."""
    return q.astype(jnp.float32) * s.astype(jnp.float32)


def _move_u16(collective, w):
    """Run a data-movement collective on a bf16 tensor's uint16 bit
    pattern. Movement never does arithmetic on the payload, and the
    bitcast keeps backends honest: XLA CPU's bf16 normalization pass
    would otherwise upcast a bf16 collective operand to f32 — doubling
    the very wire bytes the mode exists to halve (psum is the exception:
    its wire arithmetic must stay in the payload dtype)."""
    u = jax.lax.bitcast_convert_type(w, jnp.uint16)
    return jax.lax.bitcast_convert_type(collective(u), jnp.bfloat16)


def _dequant_flat_blocks(q, s, n: int, shape, dtype):
    flat = _deq(q, s[..., None]).reshape(-1)[:n]
    return flat.reshape(shape).astype(dtype)


def local_roundtrip(x, mode_: str, block: Optional[int] = None):
    """quantize→dequantize without any collective — the payload a
    compressed ppermute/all_gather delivers to its peer. The parity
    oracles in tests pin ``compressed_collective(x) ==
    exact_collective(local_roundtrip(x))`` bitwise."""
    if mode_ == "off" or not compressible(x.dtype):
        return x
    if mode_ == "bf16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    if mode_ == "int8":
        q, s = _quant_tensor(x)
        return _deq(q, s).astype(x.dtype)
    block = block or block_size()
    q, s = _quant_flat_blocks(x, block)
    return _dequant_flat_blocks(q, s, x.size, x.shape, x.dtype)


def quant_error_bound(x, mode_: str, hops: int = 1) -> float:
    """Documented per-element absolute error bound of ``hops``
    quantization steps of ``x`` under wire mode ``mode_`` — the
    tolerance the parity gates use when a lossy wire is opted in (the
    module-docstring accuracy contract as a number):

    * ``off`` (or a non-compressible dtype) — ``0.0``, bit-exact;
    * ``bf16`` — ``2^-8`` relative to the max-abs per hop (bf16 has 8
      significand bits);
    * ``int8``/``blockwise`` — one step is at most ``amax/254``
      (symmetric round-to-nearest onto ±127) per hop; blockwise bounds
      by the per-block max-abs, which this conservative form upper-
      bounds with the global max-abs.

    ``x`` may be an array or a known max-abs float. Non-finite payloads
    are outside the contract (returns ``inf``)."""
    import numpy as np

    if hasattr(x, "dtype") and not compressible(x.dtype):
        return 0.0
    amax = float(np.max(np.abs(np.asarray(x)))) if hasattr(x, "ndim") \
        else float(x)
    if not np.isfinite(amax):
        return float("inf")
    if mode_ == "off":
        return 0.0
    if mode_ == "bf16":
        return amax * (2.0 ** -8) * max(1, int(hops))
    return amax / 254.0 * max(1, int(hops))


def allreduce_wire_dtype(dtype, platform: Optional[str] = None) -> str:
    """The element type a SUMMING all-reduce of this payload actually
    moves on ``platform`` (default: the attached backend) — the
    carried-debt PR 9 caveat as a queryable table. XLA's CPU backend
    legalizes a bf16 (and f16) summing all-reduce to f32 — the wire
    moves 2x the payload bytes and the audit sees ``f32`` — while TPU
    keeps the native narrow type. Every other float payload reduces in
    its own dtype on both backends. The bench harness and the FSDP gate
    consult this so cross-tier compression claims on the emulated CPU
    mesh name the legalization instead of reporting a bare drift."""
    if platform is None:
        platform = jax.devices()[0].platform
    name = jnp.dtype(dtype).name
    wire = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
            "float64": "f64"}.get(name, name)
    if platform == "cpu" and wire in ("bf16", "f16"):
        return "f32"
    return wire


# -- shard_map-level compressed collectives -----------------------------------
# Per-shard payloads: the max-abs runs on the LOCAL block (no collective),
# and scales ride the same collective kind as the payload.


def ppermute(x, axis_name: str, perm, mode_: str, block: Optional[int] = None):
    """Compressed ``lax.ppermute``: the hop moves int8/bf16 + scales; the
    receiver dequantizes. Re-quantizing per hop means ring kernels
    compound one quantization step per hop (documented accuracy
    contract)."""
    if mode_ == "off" or not compressible(x.dtype):
        return jax.lax.ppermute(x, axis_name, perm=perm)
    if mode_ == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        hop = lambda u: jax.lax.ppermute(u, axis_name, perm=perm)  # noqa: E731
        return _move_u16(hop, w).astype(x.dtype)
    hop = lambda u: jax.lax.ppermute(u, axis_name, perm=perm)  # noqa: E731
    if mode_ == "int8":
        q, s = _quant_tensor(x)
        q = hop(q)
        s = _move_u16(hop, s)
        return _deq(q, s).astype(x.dtype)
    block = block or block_size()
    q, s = _quant_flat_blocks(x, block)
    q = hop(q)
    s = _move_u16(hop, s)
    return _dequant_flat_blocks(q, s, x.size, x.shape, x.dtype)


def all_gather(
    x, axis_name: str, mode_: str, block: Optional[int] = None,
    tiled: bool = True,
):
    """Compressed ``lax.all_gather``: every shard quantizes its block,
    gathers int8 + scales, dequantizes the full set locally."""
    if mode_ == "off" or not compressible(x.dtype):
        return jax.lax.all_gather(x, axis_name, tiled=tiled)
    gather = lambda u: jax.lax.all_gather(u, axis_name)  # noqa: E731
    if mode_ == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        return _move_u16(
            lambda u: jax.lax.all_gather(u, axis_name, tiled=tiled), w
        ).astype(x.dtype)
    if mode_ == "int8":
        q, s = _quant_tensor(x)
        qg = gather(q)                                 # (p,) + x.shape
        sg = _move_u16(gather, s)                      # (p,)
        p = qg.shape[0]
        deq = _deq(qg, sg.reshape((p,) + (1,) * x.ndim))
    else:
        block = block or block_size()
        q, s = _quant_flat_blocks(x, block)
        qg = gather(q)                                 # (p, nblk, block)
        sg = _move_u16(gather, s)                      # (p, nblk)
        p = qg.shape[0]
        deq = _deq(qg, sg[..., None]).reshape(p, -1)
        deq = deq[:, : x.size].reshape((p,) + x.shape)
    deq = deq.astype(x.dtype)
    if tiled and x.ndim >= 1:
        return deq.reshape((p * x.shape[0],) + x.shape[1:])
    return deq


def _quant_scatter_phase(x, axis_name: str, nproc: int, mode_: str,
                         block: int, groups):
    """The EQuARX FIRST phase: quantize this device's partial into
    ``nproc`` per-destination sub-chunks, all-to-all them (each device
    collects everyone's partial of its 1/p chunk), dequantize and
    accumulate in f32. Returns ``(red, chunk)`` where ``red`` is the
    f32 ``(chunk,)`` group-sum chunk this position owns — a quantized
    reduce-scatter standing alone, and the front half of the quantized
    :func:`psum`. ``groups`` (``axis_index_groups``) scopes every
    collective to a tier's replica groups (ISSUE 15); ``nproc`` is then
    the GROUP size, not the axis size."""
    n = x.size
    chunk = -(-n // nproc)
    if mode_ == "blockwise":
        block = max(1, min(block, chunk))  # no pad blowup for small chunks
        chunk = -(-chunk // block) * block
    pad_n = chunk * nproc
    flat = jnp.ravel(x).astype(jnp.float32)
    if pad_n != n:
        flat = jnp.pad(flat, (0, pad_n - n))
    parts = flat.reshape(nproc, chunk)                  # row i -> device i
    if mode_ == "int8":
        s = _scale_of(jnp.max(jnp.abs(parts)))          # scalar
        q = jnp.clip(jnp.round(parts / s), -127.0, 127.0).astype(jnp.int8)
        qt = jax.lax.all_to_all(
            q, axis_name, 0, 0, tiled=True, axis_index_groups=groups
        )
        sg = _move_u16(
            lambda u: jax.lax.all_gather(
                u, axis_name, axis_index_groups=groups
            ), s
        )                                               # (p,)
        deq = _deq(qt, sg[:, None])
    else:
        b3 = parts.reshape(nproc, chunk // block, block)
        s = _scale_of(jnp.max(jnp.abs(b3), axis=2))     # (p, nb)
        q = jnp.clip(jnp.round(b3 / s[..., None]), -127.0, 127.0)
        q = q.astype(jnp.int8)
        qt = jax.lax.all_to_all(
            q, axis_name, 0, 0, tiled=True, axis_index_groups=groups
        )
        st = _move_u16(
            lambda u: jax.lax.all_to_all(
                u, axis_name, 0, 0, tiled=True, axis_index_groups=groups
            ), s
        )
        deq = _deq(qt, st[..., None]).reshape(nproc, chunk)
    return jnp.sum(deq, axis=0), chunk                  # this device's chunk


def reduce_scatter(x, axis_name: str, nproc: int, mode_: str,
                   block: Optional[int] = None, groups=None):
    """Reduce-scatter of a payload flattened and zero-padded to ``nproc``
    equal chunks: position ``i`` (within its group) returns the 1-D
    ``(ceil(numel/nproc),)`` chunk ``i`` of the group sum, in the
    payload's dtype. ``off`` is the native ring ``lax.psum_scatter``;
    ``bf16`` the same on a bf16 payload; ``int8``/``blockwise`` the
    EQuARX first phase (:func:`_quant_scatter_phase`) standing alone —
    the ZeRO gradient-sharding primitive (ISSUE 15). Blockwise pads the
    chunk to whole blocks, so the returned chunk can be one block-pad
    longer than ``ceil(numel/nproc)``; callers slice by their own
    arithmetic."""
    n = x.size
    chunk = -(-n // nproc)
    if mode_ == "off" or not compressible(x.dtype):
        flat = jnp.ravel(x)
        if chunk * nproc != n:
            flat = jnp.pad(flat, (0, chunk * nproc - n))
        return jax.lax.psum_scatter(
            flat, axis_name, scatter_dimension=0,
            axis_index_groups=groups, tiled=True,
        )
    if mode_ == "bf16":
        flat = jnp.ravel(x).astype(jnp.bfloat16)
        if chunk * nproc != n:
            flat = jnp.pad(flat, (0, chunk * nproc - n))
        return jax.lax.psum_scatter(
            flat, axis_name, scatter_dimension=0,
            axis_index_groups=groups, tiled=True,
        ).astype(x.dtype)
    red, _chunk = _quant_scatter_phase(
        x, axis_name, nproc, mode_, block or block_size(), groups
    )
    return red.astype(x.dtype)


def _exact_psum(x, axis_name: str, groups):
    """``lax.psum``, scoped to ``groups`` when given. jax 0.9 has no
    grouped psum under shard_map's varying-axis checker (the result is
    invariant only within a group, which its types cannot say), so the
    grouped form is the textbook decomposition — reduce-scatter then
    all-gather over the same groups, the same wire bytes."""
    if groups is None:
        return jax.lax.psum(x, axis_name)
    n = x.size
    flat = x.reshape(-1)
    pad = -n % len(groups[0])
    if pad:
        flat = jnp.pad(flat, (0, pad))
    s = jax.lax.psum_scatter(
        flat, axis_name, scatter_dimension=0, axis_index_groups=groups,
        tiled=True,
    )
    out = jax.lax.all_gather(
        s, axis_name, axis_index_groups=groups, tiled=True
    )
    return out[:n].reshape(x.shape)


def psum(x, axis_name: str, nproc: int, mode_: str,
         block: Optional[int] = None, groups=None):
    """Compressed ``lax.psum`` — the EQuARX two-phase quantized
    all-reduce. ``bf16`` keeps the native all-reduce on a bf16 payload;
    ``int8``/``blockwise`` run quantize → all-to-all (each device
    collects everyone's partial of its 1/p chunk) → dequantize +
    accumulate in f32 → requantize → all-gather → dequantize. Two int8
    passes instead of one f32 ring: ``2·(B/4)·(p-1)`` wire bytes, a 4x
    reduction, at ≤ (p+1) quantization steps of error per element.
    ``groups`` scopes every collective to ``axis_index_groups`` (the
    ISSUE 15 cross-node tier); ``nproc`` is then the group size."""
    if mode_ == "off" or not compressible(x.dtype):
        return _exact_psum(x, axis_name, groups)
    if mode_ == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        return _exact_psum(w, axis_name, groups).astype(x.dtype)
    block = block or block_size()
    n = x.size
    red, chunk = _quant_scatter_phase(
        x, axis_name, nproc, mode_, block, groups
    )
    if mode_ == "blockwise":
        block = max(1, min(block, -(-n // nproc)))
    if mode_ == "int8":
        s2 = _scale_of(jnp.max(jnp.abs(red)))
        q2 = jnp.clip(jnp.round(red / s2), -127.0, 127.0).astype(jnp.int8)
        q2g = jax.lax.all_gather(
            q2, axis_name, axis_index_groups=groups
        )                                               # (p, chunk)
        s2g = _move_u16(
            lambda u: jax.lax.all_gather(
                u, axis_name, axis_index_groups=groups
            ), s2
        )                                               # (p,)
        out = _deq(q2g, s2g[:, None])
    else:
        rb = red.reshape(chunk // block, block)
        s2 = _scale_of(jnp.max(jnp.abs(rb), axis=1))
        q2 = jnp.clip(jnp.round(rb / s2[:, None]), -127.0, 127.0)
        q2 = q2.astype(jnp.int8)
        q2g = jax.lax.all_gather(
            q2, axis_name, axis_index_groups=groups
        )                                               # (p, nb, block)
        s2g = _move_u16(
            lambda u: jax.lax.all_gather(
                u, axis_name, axis_index_groups=groups
            ), s2
        )                                               # (p, nb)
        out = _deq(q2g, s2g[..., None]).reshape(nproc, chunk)
    return out.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


def pmean(x, axis_name: str, nproc: int, mode_: str,
          block: Optional[int] = None):
    """Compressed mean: compressed :func:`psum` divided by the axis size
    in the payload's compute dtype (f32 for f32 payloads)."""
    if mode_ == "off" or not compressible(x.dtype):
        return jax.lax.pmean(x, axis_name)
    return (psum(x, axis_name, nproc, mode_, block) / nproc).astype(x.dtype)


def all_to_all(
    x, axis_name: str, nproc: int, split_axis: int, concat_axis: int,
    mode_: str, block: Optional[int] = None,
):
    """Compressed tiled ``lax.all_to_all``. Each outgoing slab (the 1/p of
    the split axis headed to one peer) is quantized independently —
    per-slab scales in ``int8`` mode, per-slab flat blocks in
    ``blockwise`` — and the scales ride their own (tiny) all-to-all, so
    every receiver can dequantize its slabs by source."""
    if mode_ == "off" or not compressible(x.dtype):
        return jax.lax.all_to_all(
            x, axis_name, split_axis, concat_axis, tiled=True
        )
    if mode_ == "bf16":
        w = x if x.dtype == jnp.bfloat16 else x.astype(jnp.bfloat16)
        return _move_u16(
            lambda u: jax.lax.all_to_all(
                u, axis_name, split_axis, concat_axis, tiled=True
            ),
            w,
        ).astype(x.dtype)
    block = block or block_size()
    w = x.shape[split_axis] // nproc
    xm = jnp.moveaxis(x, split_axis, 0)                 # (S, *rest)
    rest = xm.shape[1:]
    m = w
    for d in rest:
        m *= d
    slabs = xm.reshape(nproc, m)                        # slab i -> peer i
    if mode_ == "int8":
        nb, seg = 1, m
    else:
        seg = max(1, min(block, m))  # no pad blowup for small slabs
        nb = max(1, -(-m // seg))
        if nb * seg != m:
            slabs = jnp.pad(slabs, ((0, 0), (0, nb * seg - m)))
    b3 = slabs.reshape(nproc, nb, seg)
    s = _scale_of(jnp.max(jnp.abs(b3), axis=2))         # (p, nb)
    q = jnp.clip(jnp.round(b3 / s[..., None]), -127.0, 127.0).astype(jnp.int8)
    qt = jax.lax.all_to_all(q, axis_name, 0, 0, tiled=True)
    st = _move_u16(
        lambda u: jax.lax.all_to_all(u, axis_name, 0, 0, tiled=True), s
    )
    deq = _deq(qt, st[..., None]).reshape(nproc, -1)[:, :m]
    deq = deq.reshape((nproc, w) + rest)
    # restore each slab's original axis order (w sits where split_axis was),
    # then merge the leading source axis into the concat axis source-major —
    # exactly the tiled all_to_all layout
    deq = jnp.moveaxis(deq, 1, 1 + split_axis)
    deq = jnp.moveaxis(deq, 0, concat_axis)
    shp = list(deq.shape)
    shp[concat_axis : concat_axis + 2] = [
        shp[concat_axis] * shp[concat_axis + 1]
    ]
    return deq.reshape(shp).astype(x.dtype)


# -- GSPMD-level compressed reshard -------------------------------------------


def gspmd_reshard(
    b, comm, src_split: Optional[int], dst_split: Optional[int],
    mode_: str, block: Optional[int] = None,
):
    """Inside a jit program: move ``b`` (sharded along ``src_split``) to
    the ``dst_split`` canonical layout with the wire payload compressed.

    The trick is a constraint PAIR: the quantized tensor is pinned to the
    *source* sharding first and to the destination sharding second, so
    GSPMD has no freedom to hoist the resharding collective onto the
    uncompressed input (one constraint alone lets the partitioner
    reshard the f32 operand and cast locally — measured on XLA CPU). The
    collective (all-to-all for split→split, all-gather for
    split→replicated) therefore moves the int8/bf16 payload;
    dequantization happens after, already in the destination layout.
    Scales:

    * per-tensor (``int8``, and ``blockwise`` on shapes where the block
      axis would be the sharded one): the max-abs over the sharded array
      costs one scalar cross-shard **max all-reduce** (8·(p-1) audited
      wire bytes) and the resulting scalar is replicated for free;
    * ``blockwise`` (blocks along the last, unsharded axis — see
      :func:`blockwise_segments`): scales are computed shard-locally and
      replicated by one small **all-gather**.

    The analytic prediction (`telemetry.collectives.relayout_cost` with
    ``precision=``) names these exact compounds, so the HLO audit stays
    zero-drift."""
    ndim = b.ndim
    tgt = (
        comm.sharding(dst_split, ndim)
        if dst_split is not None
        else comm.replicated()
    )

    def move(w, src_sharding, out=None):
        w = jax.lax.with_sharding_constraint(w, src_sharding)
        return jax.lax.with_sharding_constraint(
            w, out if out is not None else tgt
        )

    def move_bf16(w, src_sharding, out=None):
        # a bf16 payload travels as its uint16 bit pattern: the algebraic
        # simplifier folds a narrow-cast/up-cast pair across the
        # constraints into one f32 reduce-precision (putting the f32
        # tensor back on the wire — measured on XLA CPU), but a bitcast
        # is opaque to it, so the collective is pinned to the 2-byte
        # dtype
        u = jax.lax.bitcast_convert_type(w, jnp.uint16)
        u = move(u, src_sharding, out)
        return jax.lax.bitcast_convert_type(u, jnp.bfloat16)

    src_sh = comm.sharding(src_split, ndim)
    if mode_ == "bf16":
        w = b if b.dtype == jnp.bfloat16 else b.astype(jnp.bfloat16)
        return move_bf16(w, src_sh).astype(b.dtype)
    block = block or block_size()
    if mode_ == "blockwise" and blockwise_axis_ok(b.shape, src_split):
        nb, seg = blockwise_segments(b.shape[-1], block)
        xb = b.astype(jnp.float32).reshape(b.shape[:-1] + (nb, seg))
        s = _scale_of(jnp.max(jnp.abs(xb), axis=-1))    # shard-local blocks
        q = jnp.clip(jnp.round(xb / s.astype(jnp.float32)[..., None]),
                     -127.0, 127.0)
        q = q.astype(jnp.int8).reshape(b.shape)
        q = move(q, src_sh)
        # scales inherit the source split (their axes are b's minus the
        # blocked last one) and replicate through the same pinned pair
        s = move_bf16(
            s, comm.sharding(src_split, s.ndim), out=comm.replicated()
        )
        deq = _deq(
            q.reshape(b.shape[:-1] + (nb, seg)), s[..., None]
        ).reshape(b.shape)
        return deq.astype(b.dtype)
    # per-tensor: the max-abs spans shards -> one scalar max all-reduce
    q, s = _quant_tensor(b)
    q = move(q, src_sh)
    return _deq(q, s).astype(b.dtype)


# -- bench probe ---------------------------------------------------------------


def bench_field(gshape: Tuple[int, ...] = (4096, 64)) -> dict:
    """The ``collective_prec`` wire-bytes-vs-accuracy frontier for BENCH
    summaries (bench.py / docs/BENCHMARKS.md): for the canonical f32
    resplit(0→1) on the live mesh, per mode — analytic predicted wire
    bytes, HLO-audited emitted wire bytes of the very program that mode
    dispatches, and the executed max relative error vs the exact
    program (amax-normalized). The active env mode is reported alongside;
    `on_chip` honesty rides on the surrounding bench summary as always."""
    import numpy as np

    from . import factories, types
    from .communication import get_comm
    from ..telemetry import hlo

    comm = get_comm()
    rng = np.random.default_rng(0)
    xn = rng.standard_normal(gshape).astype(np.float32)
    x = factories.array(xn, split=0, comm=comm)
    field = {"mode": mode(), "block": block_size(), "gshape": list(gshape),
             "modes": {}}
    ref = None
    for m in MODES:
        row = {"predicted_wire_bytes": None, "audited_wire_bytes": None,
               "max_rel_err": None}
        try:
            phys = comm.padded_shape(
                comm.padded_shape(gshape, 0), 1
            )
            row["predicted_wire_bytes"] = int(
                _cost.relayout_cost(
                    phys, 4, 0, 1, comm.size, precision=m,
                    block=block_size(),
                ).bytes
            )
            fn = x._relayout_executable(1, precision=m)
            row["audited_wire_bytes"] = int(
                hlo.audit_computation(fn, x.larray).total_wire()
            )
            out = np.asarray(fn(x.larray))
            if m == "off":
                ref = out
                row["max_rel_err"] = 0.0
            elif ref is not None:
                denom = float(np.max(np.abs(ref))) or 1.0
                row["max_rel_err"] = float(
                    np.max(np.abs(out - ref)) / denom
                )
        except Exception as e:  # pragma: no cover — probe must never kill bench
            row["error"] = repr(e)
        field["modes"][m] = row
    return field
