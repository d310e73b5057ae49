"""Ground-truth XLA collective audit — predicted vs emitted.

The analytic cost model (:mod:`.collectives`) *predicts* what XLA should
emit from the layout contract; until now nothing in the repo verified the
prediction — exactly the gap that makes redistribution costs surprising in
practice (arXiv:2112.01075) and cross-mesh resharding invisible
(arXiv:2211.05322). This module closes the loop: lower-and-compile a
jitted computation (``fn.lower(...).compile()``), parse the optimized HLO
``as_text()`` plus ``cost_analysis()`` into a structured
:class:`CollectiveAudit` — one :class:`EmittedCollective` per emitted
``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
``collective-permute``, with element type, shape, replica groups and
modeled wire bytes — and :func:`compare` the audit against the analytic
:class:`~.collectives.CollectiveCost`, flagging **drift**: wrong
primitive, extra reshard, or byte mismatch beyond tolerance.

Wire-byte models per emitted op (``g`` = participants per replica group,
``n`` = total participants across groups, payload = per-participant
tensor bytes — the same "total bytes crossing links, summed over devices"
convention as the analytic model):

====================  =====================================================
op                    total wire bytes per execution
====================  =====================================================
all-gather            ``out · (g-1)/g · n`` (each device receives the
                      ``(g-1)/g`` of the result it does not hold)
all-to-all            ``in · (g-1)/g · n`` (each keeps its own ``1/g``)
reduce-scatter        ``in · (g-1)/g · n`` (ring reduce-scatter)
all-reduce            ``2 · in · (g-1)/g · n`` (ring: reduce-scatter +
                      all-gather phase)
collective-permute    ``in · |source_target_pairs|``
====================  =====================================================

A collective inside a loop body is counted ONCE per static instruction —
the HLO text does not expose trip counts — so :func:`compare` scales
``collective-permute`` volume by the predicted ring step count when the
prediction is a ``ppermute-ring``.

Auditing is opt-in: per call (``audit=True`` on `resplit`, `qr`, `cdist`)
or globally (:func:`enable_audit` / ``HEAT_TPU_HLO_AUDIT=1``, which the
benchmark harness's ``--audit`` flag sets). Each audit is memoized on the
(site, shapes, dtype, splits, mesh) key — the lower/compile cost is paid
once per distinct program, not per call — and recorded both in this
module (:func:`last_audit`, :func:`recent`) and, when telemetry is
recording, as an ``hlo_audit`` event that :func:`..report.summarize`
aggregates into the ``hlo_collectives`` benchmark section.

The same text carries, on every instruction, ``metadata={op_name="..."}``:
the flax module path, every ``jax.named_scope`` and the pass that wrote the
instruction. :func:`scope_rows` reads it into a **scope map** (instruction
name -> modules, scopes, pass), and :func:`program_scopes` gives the map of
a program this process ran under a span (``nn.DataParallel``'s train step
notes its launches with :func:`note_launch`). A device trace names an event by
the instruction's own line, so the map joined to any profile splits a
program's device time by scope: ``docs/OBSERVABILITY.md``, "Scopes inside a
step".
"""

from __future__ import annotations

import re
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from heat_tpu import _knobs as knobs

__all__ = [
    "EmittedCollective",
    "CollectiveAudit",
    "Drift",
    "DriftReport",
    "AuditRecord",
    "parse_hlo",
    "audit_compiled",
    "audit_computation",
    "compare",
    "audit_call",
    "enable_audit",
    "disable_audit",
    "audit_enabled",
    "last_audit",
    "recent",
    "clear",
    "DEFAULT_TOLERANCE",
    "scope_rows",
    "note_launch",
    "program_scopes",
]

# Byte-drift tolerance: |emitted - predicted| / predicted beyond which a
# drift is flagged. Audit sites predict on the shapes of the program being
# audited (the kernel costs use ceil-divided blocks; the relayout audit
# pads its shape the way the lowered program does), so this covers genuine
# compiler freedom — fusion-dependent layout choices, an XLA version
# changing the decomposition — not systematic padding arithmetic. 10%
# still catches a wrong primitive or a doubled transfer outright.
DEFAULT_TOLERANCE = float(knobs.raw("HEAT_TPU_HLO_TOLERANCE", "0.1"))

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# One optimized-HLO instruction: `[ROOT] %name = <type> <opcode>(rest...`.
# The result type is either a tensor (`f32[64,32]{1,0}`) or a tuple of
# tensors (`(f32[8,1,4]{2,1,0}, ...)` — the tuple-form all-to-all). The
# opcode position (after " = <type> ") is what keeps consumer lines like
# `%gte = f32[...] get-tuple-element(... %all-to-all.1), index=0` from
# matching on their operand names.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*(?P<rtype>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVE_OPS) + r")"
    r"(?P<variant>-start|-done)?"
    r"\((?P<rest>.*)$",
    re.MULTILINE,
)

_TENSOR_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|f8e\w+|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64"
    r"|c64|c128)\[([0-9,]*)\]"
)

# Any instruction definition, `[ROOT] %name = <type> opcode(`: the
# installed XLA prints a collective's operands as bare names
# (`all-to-all(%wrapped_slice, %wrapped_slice.1)`), so operand bytes come
# from the defining instruction's result type. Instruction names are
# unique across an HLO module.
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*(?P<rtype>\([^)]*\)|\S+)\s+"
    r"[\w\-]+\(",
    re.MULTILINE,
)
_OPERAND_NAME_RE = re.compile(r"%([^\s,()]+)")

_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _itemsize(dt: str) -> int:
    if dt == "pred" or dt in ("s4", "u4", "s8", "u8") or dt.startswith("f8"):
        return 1
    if dt == "c128":
        return 16
    if dt == "c64":
        return 8
    return int(dt.lstrip("bfsu")) // 8


def _tensor_bytes(types: str) -> Tuple[int, Optional[str], Tuple[Tuple[int, ...], ...]]:
    """Sum the byte sizes of every tensor type in ``types``; also return
    the first element type and the shapes (for the audit record)."""
    total = 0
    dtype = None
    shapes: List[Tuple[int, ...]] = []
    for dt, dims in _TENSOR_RE.findall(types):
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        numel = 1
        for d in shape:
            numel *= d
        total += numel * _itemsize(dt)
        shapes.append(shape)
        if dtype is None:
            dtype = dt
    return total, dtype, tuple(shapes)


def _split_operands_attrs(rest: str) -> Tuple[str, str]:
    """Split the text after the opening ``(`` into the operand list and the
    trailing attributes (``channel_id=…, replica_groups=…, metadata=…``)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def _parse_groups(attrs: str, default_participants: Optional[int]):
    """Replica groups → (group_size, n_participants, groups tuple)."""
    m = _GROUPS_LITERAL_RE.search(attrs)
    if m:
        groups = tuple(
            tuple(int(v) for v in grp.split(",") if v.strip())
            for grp in re.findall(r"\{([^}]*)\}", m.group(1))
        )
        groups = tuple(g for g in groups if g)
        if groups:
            return max(len(g) for g in groups), sum(len(g) for g in groups), groups
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:  # iota form [num_groups, group_size]<=[n] (+ optional transpose)
        num, size = int(m.group(1)), int(m.group(2))
        return size, num * size, ((num, size),)
    n = default_participants or 1
    return n, n, ()


@dataclass(frozen=True)
class EmittedCollective:
    """One collective instruction in an optimized HLO module."""

    op: str                                  # canonical opcode
    name: str                                # HLO instruction name
    dtype: Optional[str]                     # element type, e.g. "f32"
    shapes: Tuple[Tuple[int, ...], ...]      # result tensor shape(s)
    in_bytes: int                            # per-participant operand bytes
    out_bytes: int                           # per-participant result bytes
    group_size: int                          # participants per replica group
    n_participants: int                      # total participants
    groups: Tuple                            # replica groups / st-pairs
    wire_bytes: int                          # modeled total wire bytes
    op_name: str = ""                        # XLA metadata provenance

    def summary(self) -> dict:
        return {
            "op": self.op,
            "name": self.name,
            "dtype": self.dtype,
            "shapes": [list(s) for s in self.shapes],
            "in_bytes": self.in_bytes,
            "out_bytes": self.out_bytes,
            "group_size": self.group_size,
            "wire_bytes": self.wire_bytes,
        }


def _wire_bytes(op: str, in_bytes: int, out_bytes: int, g: int, n: int,
                n_pairs: int) -> int:
    if op == "collective-permute":
        return in_bytes * n_pairs
    if g <= 1:
        return 0
    if op == "all-gather":
        return out_bytes * (g - 1) * n // g
    if op == "all-reduce":
        return 2 * in_bytes * (g - 1) * n // g
    # all-to-all and reduce-scatter: each participant ships the (g-1)/g of
    # its input destined elsewhere
    return in_bytes * (g - 1) * n // g


def parse_hlo(
    text: str, default_participants: Optional[int] = None
) -> List[EmittedCollective]:
    """Parse optimized HLO text into the emitted-collective records.

    Tolerant to XLA version noise: only the instruction grammar
    (``%name = type opcode(...)``) and the ``replica_groups`` /
    ``source_target_pairs`` attribute syntax are relied on. Async pairs
    count once (the ``-start`` carries the payload; ``-done`` is skipped).
    ``default_participants`` seeds the group size when an instruction
    carries no replica_groups attribute (flat single-group default).
    """
    out: List[EmittedCollective] = []
    defs: Optional[Dict[str, str]] = None
    for m in _INSTR_RE.finditer(text):
        if m.group("variant") == "-done":
            continue
        op = m.group("op")
        operands, attrs = _split_operands_attrs(m.group("rest"))
        if not _TENSOR_RE.search(operands):
            if defs is None:
                defs = {
                    d.group("name"): d.group("rtype")
                    for d in _DEF_RE.finditer(text)
                }
            operands = " ".join(
                defs.get(n, "") for n in _OPERAND_NAME_RE.findall(operands)
            )
        in_bytes, in_dtype, _ = _tensor_bytes(operands)
        out_bytes, out_dtype, shapes = _tensor_bytes(m.group("rtype"))
        if m.group("variant") == "-start" and in_bytes <= out_bytes:
            # async form: the start's tuple result aliases the operand
            # buffer(s) alongside the actual result — counting both would
            # inflate the all-gather wire model past the drift tolerance
            out_bytes -= in_bytes
            shapes = shapes[1:] if len(shapes) > 1 else shapes
        pairs: Tuple = ()
        if op == "collective-permute":
            pm = _PAIRS_RE.search(attrs)
            if pm:
                pairs = tuple(
                    tuple(int(v) for v in pair.split(","))
                    for pair in re.findall(r"\{(\d+,\d+)\}", pm.group(1))
                )
            g = n = len({d for pr in pairs for d in pr}) or (
                default_participants or 1
            )
            groups: Tuple = pairs
        else:
            g, n, groups = _parse_groups(attrs, default_participants)
        om = _OP_NAME_RE.search(attrs)
        out.append(
            EmittedCollective(
                op=op,
                name=m.group("name"),
                dtype=out_dtype or in_dtype,
                shapes=shapes,
                in_bytes=in_bytes,
                out_bytes=out_bytes,
                group_size=g,
                n_participants=n,
                groups=groups,
                wire_bytes=_wire_bytes(op, in_bytes, out_bytes, g, n, len(pairs)),
                op_name=om.group(1) if om else "",
            )
        )
    return out


@dataclass
class CollectiveAudit:
    """The collectives one compiled XLA program will execute."""

    collectives: List[EmittedCollective]
    n_devices: int = 1
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None

    def counts(self) -> Dict[str, int]:
        """Static instruction count per opcode."""
        out: Dict[str, int] = {}
        for c in self.collectives:
            out[c.op] = out.get(c.op, 0) + 1
        return out

    def wire_by_op(self) -> Dict[str, int]:
        """Modeled wire bytes per opcode (per single execution of each
        instruction — loop trip counts are not included, see module doc)."""
        out: Dict[str, int] = {}
        for c in self.collectives:
            out[c.op] = out.get(c.op, 0) + c.wire_bytes
        return out

    def total_wire(self) -> int:
        return sum(c.wire_bytes for c in self.collectives)

    def summary(self) -> dict:
        s = {
            "ops": self.counts(),
            "wire_bytes": self.wire_by_op(),
            "instructions": [c.summary() for c in self.collectives],
            "n_devices": self.n_devices,
        }
        if self.flops is not None:
            s["flops"] = self.flops
        if self.bytes_accessed is not None:
            s["bytes_accessed"] = self.bytes_accessed
        return s


def audit_compiled(compiled, n_devices: Optional[int] = None) -> CollectiveAudit:
    """Audit an already-compiled executable (``jit(f).lower(...).compile()``)."""
    if n_devices is None:
        n_devices = jax.device_count()
    flops = bytes_accessed = None
    try:
        ca = compiled.cost_analysis()
        props = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
        if isinstance(props, dict):
            flops = props.get("flops")
            bytes_accessed = props.get("bytes accessed")
    except Exception:  # pragma: no cover — cost analysis is best-effort
        pass
    return CollectiveAudit(
        collectives=parse_hlo(compiled.as_text(), default_participants=n_devices),
        n_devices=n_devices,
        flops=flops,
        bytes_accessed=bytes_accessed,
    )


def audit_computation(fn, *args, **kwargs) -> CollectiveAudit:
    """Lower-and-compile ``fn(*args, **kwargs)`` (a jitted or jittable
    callable — sharded example arguments determine the input layouts) and
    audit the compiled program. Compiles but never executes."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return audit_compiled(jitted.lower(*args, **kwargs).compile())


# -- predicted-vs-emitted drift ----------------------------------------------

# analytic CollectiveCost.kind (possibly "+"-compound) → expected HLO opcode
_KIND_TO_OP = {
    "all-gather": "all-gather",
    "all-to-all": "all-to-all",
    "ppermute-ring": "collective-permute",
    "all-reduce": "all-reduce",
    "reduce-scatter": "reduce-scatter",
    "none": None,
    "local-slice": None,
}


@dataclass(frozen=True)
class Drift:
    """One predicted-vs-emitted discrepancy."""

    reason: str          # "missing-collective" | "unexpected-collective"
    #                    # | "byte-drift" | "unknown-kind"
    op: str
    predicted_bytes: int
    emitted_bytes: int
    detail: str

    def summary(self) -> dict:
        return {
            "reason": self.reason,
            "op": self.op,
            "predicted_bytes": self.predicted_bytes,
            "emitted_bytes": self.emitted_bytes,
            "detail": self.detail,
        }


@dataclass
class DriftReport:
    """Outcome of one :func:`compare`: ``ok`` iff no drift was flagged."""

    ok: bool
    drifts: List[Drift]
    expected_ops: Tuple[str, ...]
    predicted_bytes: int
    emitted_bytes: int       # steps-scaled total over the expected ops
    tolerance: float

    def summary(self) -> dict:
        return {
            "ok": self.ok,
            "expected_ops": list(self.expected_ops),
            "predicted_bytes": self.predicted_bytes,
            "emitted_bytes": self.emitted_bytes,
            "tolerance": self.tolerance,
            "drifts": [d.summary() for d in self.drifts],
        }


def compare(
    audit: CollectiveAudit,
    predicted,
    tolerance: Optional[float] = None,
    steps: Optional[int] = None,
) -> DriftReport:
    """Diff an audit against the analytic prediction for the same program.

    ``predicted`` is a :class:`~.collectives.CollectiveCost`. Flags:

    * **missing-collective** — the predicted primitive never appears;
    * **unexpected-collective** — an emitted collective the prediction
      does not name (e.g. an extra reshard XLA slipped in);
    * **byte-drift** — total emitted wire bytes over the expected ops
      differ from the predicted volume by more than ``tolerance``
      (relative; default :data:`DEFAULT_TOLERANCE`).

    Ring predictions (``ppermute-ring``) have their emitted
    ``collective-permute`` volume scaled by the predicted ``steps`` —
    the loop trip count the HLO text cannot express.
    """
    tolerance = DEFAULT_TOLERANCE if tolerance is None else tolerance
    steps = predicted.steps if steps is None else steps
    parts = predicted.kind.split("+")
    expected: List[str] = []
    drifts: List[Drift] = []
    for part in parts:
        if part not in _KIND_TO_OP:
            drifts.append(
                Drift("unknown-kind", part, predicted.bytes, 0,
                      f"analytic kind {part!r} has no HLO opcode mapping")
            )
            continue
        op = _KIND_TO_OP[part]
        if op is not None:
            expected.append(op)

    emitted_total = 0
    for op in dict.fromkeys(expected):  # unique, order-preserving
        instrs = [c for c in audit.collectives if c.op == op]
        if not instrs:
            drifts.append(
                Drift("missing-collective", op, predicted.bytes, 0,
                      f"predicted {predicted.kind!r} but the compiled "
                      f"program contains no {op}")
            )
            continue
        wire = sum(c.wire_bytes for c in instrs)
        if op == "collective-permute" and steps > 1:
            wire *= steps
        emitted_total += wire

    for c in audit.collectives:
        if c.op not in expected:
            drifts.append(
                Drift("unexpected-collective", c.op, 0, c.wire_bytes,
                      f"{c.name}: emitted {c.op} not named by the "
                      f"prediction {predicted.kind!r}")
            )

    if expected and not any(d.reason == "missing-collective" for d in drifts):
        pb = int(predicted.bytes)
        if pb > 0 and abs(emitted_total - pb) > tolerance * pb:
            drifts.append(
                Drift("byte-drift", "+".join(dict.fromkeys(expected)), pb,
                      emitted_total,
                      f"emitted {emitted_total} wire bytes vs predicted "
                      f"{pb} (beyond {tolerance:.0%} tolerance)")
            )

    return DriftReport(
        ok=not drifts,
        drifts=drifts,
        expected_ops=tuple(dict.fromkeys(expected)),
        predicted_bytes=int(predicted.bytes),
        emitted_bytes=emitted_total,
        tolerance=tolerance,
    )


# -- opt-in auditing at instrumented sites ------------------------------------

_AUDIT_ENABLED = False
_CACHE: Dict[Any, CollectiveAudit] = {}
_RECENT: "deque[AuditRecord]" = deque(maxlen=64)


@dataclass
class AuditRecord:
    """One recorded audit at an instrumented site."""

    site: str
    audit: CollectiveAudit
    report: Optional[DriftReport] = None
    fields: Dict[str, Any] = field(default_factory=dict)

    def summary(self) -> dict:
        s = {"site": self.site, **self.fields}
        s["audit"] = self.audit.summary()
        s["report"] = self.report.summary() if self.report else None
        return s


def audit_enabled() -> bool:
    """Whether the global opt-in (``HEAT_TPU_HLO_AUDIT=1`` /
    :func:`enable_audit`) is active; instrumented ops also audit when
    called with ``audit=True`` explicitly."""
    return _AUDIT_ENABLED


def enable_audit() -> None:
    global _AUDIT_ENABLED
    _AUDIT_ENABLED = True


def disable_audit() -> None:
    global _AUDIT_ENABLED
    _AUDIT_ENABLED = False


def clear() -> None:
    """Drop the memo cache, the recent-audit ring and the noted launches."""
    _CACHE.clear()
    _RECENT.clear()
    _LAUNCHED.clear()


def recent() -> List[AuditRecord]:
    """The most recent audits (bounded ring), oldest first."""
    return list(_RECENT)


def last_audit(site: Optional[str] = None) -> Optional[AuditRecord]:
    """The most recent audit, optionally filtered by site name."""
    for rec in reversed(_RECENT):
        if site is None or rec.site == site:
            return rec
    return None


def audit_call(
    site: str,
    build,
    predicted=None,
    key: Optional[Any] = None,
    fields: Optional[Dict[str, Any]] = None,
    tolerance: Optional[float] = None,
) -> Optional[AuditRecord]:
    """Audit one instrumented call site; never raises.

    ``build()`` returns ``(jittable_or_jitted, args_tuple)`` — the
    equivalent single-program computation to lower and compile (sharded
    example args pin the input layouts). Memoized on ``key`` so repeated
    calls with the same program shape pay the compile once. The record
    lands in :func:`recent`, and — when telemetry is recording — as an
    ``hlo_audit`` event with the emitted op counts/bytes and the drift
    verdict against ``predicted``.
    """
    audit = _CACHE.get(key) if key is not None else None
    if audit is None:
        try:
            fn, args = build()
            audit = audit_computation(fn, *args)
        except Exception as e:
            # the auditor observes; it must never take the workload down
            warnings.warn(f"heat_tpu.telemetry.hlo: audit of {site!r} "
                          f"failed ({e!r}); skipping")
            return None
        if key is not None:
            _CACHE[key] = audit
    report = (
        compare(audit, predicted, tolerance=tolerance)
        if predicted is not None
        else None
    )
    rec = AuditRecord(site=site, audit=audit, report=report,
                      fields=dict(fields or {}))
    _RECENT.append(rec)

    from . import enabled, get_registry

    if enabled():
        ev: Dict[str, Any] = {
            "ops": audit.counts(),
            "bytes_by_op": audit.wire_by_op(),
        }
        if report is not None:
            ev.update(
                predicted=predicted.kind,
                predicted_bytes=int(predicted.bytes),
                emitted_bytes=report.emitted_bytes,
                drift=len(report.drifts),
                ok=report.ok,
            )
            if report.drifts:
                ev["drifts"] = [d.summary() for d in report.drifts]
        else:
            ev["emitted_bytes"] = audit.total_wire()
        ev.update(fields or {})
        get_registry().emit("hlo_audit", site, **ev)
    return rec


# -- the scope map: instruction name -> module path, named scopes, pass --------
#
# An ``op_name`` is the name stack JAX kept for the equation an instruction
# came from, ``/``-joined, the primitive last. The spellings below are those of
# the three published-width train steps compiled for a TPU v5e on jax 0.9.0
# (pinned in ``tests/test_train_steps_tpu_compile.py``):
#
#   jit(dp_train_step)/jvp(lm.body)/TransformerLM/block3/attn/attn.window/slice
#       forward;
#   jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/block0/ln1/mul
#       backward, no checkpoint;
#   .../transpose(jvp(lm.body))/TransformerLM/jvp(lm.body)/TransformerLM/checkpoint/block3/moe/moe.route/gather
#       backward under ``nn.remat``: the transposed equation's own stack follows
#       the stack of the place where the transpose ran, from its root again;
#   .../jvp(lm.body)/TransformerLM/checkpoint/rematted_computation/block3/ln1/mul
#       the block run again in the backward pass: recomputed;
#   .../checkpoint/block1/gdn/while/body/closed_call/checkpoint/rematted_computation/gdn.conv/mul
#       a ``jax.checkpoint`` inside a ``lax.map`` inside a rematerialised block;
#   .../while/body/transpose(jvp(moe.combine))/mul, .../while/body/jvp(moe.route)/gather
#       a custom VJP's backward rule that differentiates inside a loop.
#
# A transform wraps the stack's elements (``jvp(lm.body)``), a jitted function
# inside the step reads ``jit(silu)``, and JAX's own frames are plain words.
# Flax opens a ``jax.named_scope`` of a module's name, so nothing but the
# spelling tells a module from a scope: a scope of this repo is dotted
# (``moe.route``), a flax name never is.

_COMPUTATION_RE = re.compile(r"^(?P<entry>ENTRY\s+)?%?(?P<name>[^\s(]+)\s+\(.*->.*\{\s*$")
_ANY_INSTR_RE = re.compile(r"^\s+(?P<root>ROOT\s+)?%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$")
# the opcode: the first `` word(`` behind the closing bracket of the result's type
_OPCODE_RE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_CALLED_RE = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)=%?([^\s,)}]+)"
)
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WRAPPED_RE = re.compile(r"^(\w+)\((.*)\)$")
_IDENTIFIER_RE = re.compile(r"^[A-Za-z_]\w*$")
_BRANCH_FRAME_RE = re.compile(r"^branch_\d+_fun$")
# JAX's own frames in a name stack: control flow, closed calls, checkpoints
_FRAMES = frozenset({"while", "body", "cond", "closed_call", "checkpoint", "rematted_computation"})
# flax's own frame for a module's method other than ``__call__`` or for a function it lifts over a module
# (``TransformerLM._looped``, ``TransformerLM.one_pass``): a class name before the dot, where a scope's is lower case
_METHOD_FRAME_RE = re.compile(r"^[A-Z]\w*\.\w+$")
# the instructions whose called computations hold instructions of their own
# that run (and show in a device trace); a fusion's are folded into its row
_REACHES = {
    "while": ("body", "condition"),
    "call": ("to_apply",),
    "conditional": ("true_computation", "false_computation", "branches"),
}

_NO_SCOPE: Dict[str, Any] = {"path": "", "modules": "", "scopes": (), "pass": ""}


def _unwrap(component: str) -> Tuple[List[str], str]:
    """``transpose(jvp(lm.body))`` -> (``["transpose", "jvp"]``, ``"lm.body"``)."""
    wrappers: List[str] = []
    m = _WRAPPED_RE.match(component)
    while m:
        wrappers.append(m.group(1))
        component = m.group(2)
        m = _WRAPPED_RE.match(component)
    return wrappers, component


def split_op_name(op_name: str) -> Dict[str, Any]:
    """One ``op_name`` path as ``{"path", "modules", "scopes", "pass"}``:
    ``modules`` the flax path (``TransformerLM/block3/attn/query``), ``scopes``
    the dotted ``jax.named_scope``s on it, outermost first, transforms taken
    off (``("lm.body", "attn.window")``), ``pass`` one of ``forward``,
    ``recomputed`` (a checkpoint's rematerialised computation, which runs in
    the backward pass) and ``backward`` (under a ``transpose(...)``). What
    lies outside every transform (the optimizer) reads ``forward``. An empty
    path gives empty fields."""
    if not op_name:
        return dict(_NO_SCOPE)
    # XLA joins the names of two instructions it folds into one with ";", the second cut to where they part
    parts = op_name.split(";")[0].split("/")
    if "rematted_computation" in parts:
        which = "recomputed"
    elif any(c.startswith("transpose(") for c in parts):
        which = "backward"
    else:
        which = "forward"
    # under a checkpoint the transposed equation brings its own stack, from the
    # root again, behind that of the place where the transpose ran: keep one
    for i, c in enumerate(parts):
        if c.startswith("transpose(") and c[len("transpose("):-1] in parts[i + 1:]:
            parts = parts[:i + 1] + parts[parts.index(c[len("transpose("):-1], i + 1):]
            break
    modules: List[str] = []
    scopes: List[str] = []
    named = parts[:-1]  # the last is the primitive
    if parts[-1] == "pallas_call":
        named = named[:-1]  # and a Pallas kernel stands behind its own ``name=``, which is no module of the model
    for c in named:
        wrappers, name = _unwrap(c)
        if "jit" in wrappers or name in _FRAMES or _BRANCH_FRAME_RE.match(name) or _METHOD_FRAME_RE.match(name):
            continue
        if "." in name:
            if name not in scopes:
                scopes.append(name)
        elif _IDENTIFIER_RE.match(name):
            modules.append(name)
    return {"path": op_name, "modules": "/".join(modules), "scopes": tuple(scopes), "pass": which}


def _computations(text: str):
    """``(entry, {computation: [instruction]})`` of an optimized module's
    text, an instruction as ``{"name", "op", "root", "op_name", "called"}``."""
    entry, comps, current = None, {}, None
    for line in text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            m = _COMPUTATION_RE.match(line)
            current = None
            if m:
                current = comps.setdefault(m.group("name"), [])
                if m.group("entry"):
                    entry = m.group("name")
            continue
        if current is None:
            continue
        m = _ANY_INSTR_RE.match(line)
        if not m:
            continue
        rest = m.group("rest")
        op = _OPCODE_RE.search(rest)
        operands = _split_operands_attrs(rest[op.end():])[0] if op else ""
        called = {k: [v] for k, v in _CALLED_RE.findall(rest)}
        branches = _BRANCHES_RE.search(rest)
        if branches:
            called["branches"] = [b.strip().lstrip("%") for b in branches.group(1).split(",") if b.strip()]
        name = _OP_NAME_RE.search(rest)
        current.append({
            "name": m.group("name"), "op": op.group(1) if op else "", "root": bool(m.group("root")),
            "op_name": name.group(1) if name else "", "called": called,
            "operands": _OPERAND_NAME_RE.findall(operands),
        })
    return entry, comps


def _nearest_named(start: str, named: set, users: dict, operands: dict, hops: int = 4) -> Optional[str]:
    """The nearest instruction of ``named`` to ``start`` in its computation:
    through what uses it first (a copy belongs to what it feeds), then through
    what it reads, a few steps at most."""
    for nexts in (users, operands):
        frontier, seen = [start], {start}
        for _ in range(hops):
            reached = []
            for n in frontier:
                for m in nexts.get(n, ()):
                    if m in named:
                        return m
                    if m not in seen:
                        seen.add(m)
                        reached.append(m)
            frontier = reached
    return None


def scope_rows(text: str) -> Dict[str, Dict[str, Any]]:
    """The scope map of a compiled module's ``as_text()``: a row for every
    instruction of the entry computation and of every computation a ``while``,
    ``call`` or ``conditional`` reaches from it, by the instruction's name (a
    device event's name is the instruction's line, whose head is this name and
    is unique in its module).

    A row is :func:`split_op_name` of the instruction's ``op_name`` with its
    opcode as ``op``. A ``fusion``'s row is its own (XLA gives a fusion its
    root's metadata; the root's where it has none, and where the root is a
    bitcast or tuple the compiler added, the last fused instruction's that has
    any) and lists as ``fused`` the distinct ``(modules, scopes)`` of the fused
    computation's instructions, so that a reader can tell a fusion that mixes
    pieces. An instruction without
    metadata (a copy, bitcast or zero fill the compiler added, a parameter)
    has empty fields; where an instruction of its computation that has some is
    near (what uses it, else what it reads, through at most four others without),
    the row names it as ``via``: a reader may lend the copy that row."""
    entry, comps = _computations(text)
    rows: Dict[str, Dict[str, Any]] = {}
    todo, seen = [entry], {entry}
    while todo:
        body = comps.get(todo.pop(), ())
        for ins in body:
            op_name, fused = ins["op_name"], ()
            if ins["op"] == "fusion":
                inside = [i for c in ins["called"].get("calls", ()) for i in comps.get(c, ())]
                named = [i["op_name"] for i in inside if i["op_name"]]
                if not op_name:  # the root's; a root the compiler added (a bitcast, a tuple) has none: the last named one's
                    op_name = next((i["op_name"] for i in inside if i["root"]), "") or (named[-1] if named else "")
                found = {}
                for name in named:
                    r = split_op_name(name)
                    found[(r["modules"], r["scopes"])] = None
                fused = tuple(found)
            rows[ins["name"]] = {"op": ins["op"], **split_op_name(op_name), "fused": fused}
            for key in _REACHES.get(ins["op"], ()):
                for c in ins["called"].get(key, ()):
                    if c not in seen:
                        seen.add(c)
                        todo.append(c)
        mine = [i["name"] for i in body]
        named = {n for n in mine if rows[n]["path"]}
        if named and len(named) < len(mine):
            operands = {i["name"]: i["operands"] for i in body}
            users: Dict[str, List[str]] = {}
            for i in body:
                for o in i["operands"]:
                    users.setdefault(o, []).append(i["name"])
            for n in mine:
                if n not in named:
                    via = _nearest_named(n, named, users, operands)
                    if via is not None:
                        rows[n]["via"] = via
    return rows


# -- the map of a program this process ran -------------------------------------


@dataclass
class _Launched:
    lower: Any        # the jitted program's ``lower``
    variants: Any     # how many signatures it had traced when this one was kept
    signature: Any    # the call's arguments as ``jax.ShapeDtypeStruct``s: no arrays
    rows: Optional[Dict[str, Dict[str, Any]]] = None


_LAUNCHED: Dict[str, _Launched] = {}


def _variants(lower) -> Optional[int]:
    """Signatures the jitted function behind ``lower`` has traced: a retrace
    adds one. None where this jax does not say."""
    size = getattr(getattr(lower, "__self__", None), "_cache_size", None)
    return size() if callable(size) else None


def _abstract(leaf):
    if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
        return leaf
    # an uncommitted array leaves its placement to the program, as the call did
    sharding = leaf.sharding if getattr(leaf, "committed", False) else None
    return jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=sharding, weak_type=getattr(leaf, "weak_type", False)
    )


def note_launch(site: str, program, args) -> None:
    """Keep, once a program, the abstract signature (shape, dtype, sharding of
    each leaf; no array) of a call of the jitted ``program`` at ``site``, so
    that :func:`program_scopes` can ask for the text of what ran. A launch
    wrapper calls this **only while its span records**: with telemetry off and
    no profile live nothing is kept. A retrace (the jitted function holds one
    signature more than when the last was kept) drops what was kept, the map
    with it, and keeps the new call's."""
    lower = program.lower
    variants = _variants(lower)
    kept = _LAUNCHED.get(site)
    if kept is not None and variants is not None and kept.variants == variants and kept.lower == lower:
        return
    _LAUNCHED[site] = _Launched(lower, variants, jax.tree.map(_abstract, args))
    from . import get_registry

    get_registry().add(f"hlo.launches_noted.{site}", 1)


def program_scopes(site: str) -> Optional[Dict[str, Dict[str, Any]]]:
    """:func:`scope_rows` of the program that ran at ``site`` (the train step:
    ``"dp_train_step"``), or None where no launch of it was noted. Lowers and
    compiles from the kept signature on the first request, after the fact: the
    jitted function answers from what it compiled for the call itself (no
    backend compile; else JAX's persistent cache, else a compile), and the rows
    are kept. The text is the executable's: one loaded from the persistent
    cache carries the names of the source that compiled it there, whose key
    leaves metadata out. Never raises."""
    kept = _LAUNCHED.get(site)
    if kept is None:
        return None
    if kept.rows is None:
        from ..core.program_cache import _DONATION_NOISE

        try:
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=_DONATION_NOISE)
                kept.rows = scope_rows(kept.lower(*kept.signature).compile().as_text())
        except Exception as e:  # the map observes; it must never take the workload down
            warnings.warn(f"heat_tpu.telemetry.hlo: no scope map of {site!r} ({e!r})")
            return None
    return kept.rows


# Environment activation (mirrors HEAT_TPU_TELEMETRY): the benchmark
# harness's --audit flag and the CI audit step set this before import.
if knobs.raw("HEAT_TPU_HLO_AUDIT", "").strip().lower() in (
    "1", "true", "yes", "on",
):
    _AUDIT_ENABLED = True
