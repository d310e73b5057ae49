"""The train step's device time by piece and pass: the program's own scope map
(``heat_tpu.telemetry.hlo.program_scopes``: instruction name -> flax module
path, named scopes, pass; made from the compiled step's metadata) joined to the
device trace, whose events are named by the instruction's own line.

Only leaf events are counted (``trace_reduce.leaves``: a ``while`` holds its
body's operations and is not counted on top of them), and only those that lie
inside a program named ``jit_dp_train_step`` on the modules line: another
program's ``%fusion.3`` is not the step's. A leaf goes to the first row of
``PIECES`` that its map row matches (an instruction without metadata, a copy or
zero fill the compiler added, borrows the row of the neighbour the map names as
``via``: ``lent_ms`` says how much was placed so); one without a row, with empty
fields and no such neighbour, or that no row of the table matches goes to
``unscoped``.

A program without the map (a parent commit, a program whose launches were not
noted) gives ``None`` everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import bisect
import re
import time
from typing import Dict, List, Optional, Tuple

from chipbench import trace_reduce

SITE = "dp_train_step"
STEP_MODULE = re.compile(r"^jit_dp_train_step\b")
UNSCOPED = "unscoped"

# piece, which field of a map row, pattern (searched; ``scopes`` joined by a
# blank, outermost first). First match wins, so the order is part of the data:
# a scope that names a piece stands before the module paths that hold it.
PIECES: Tuple[Tuple[str, str, str], ...] = (
    ("head_loss", "scopes", r"\blm\.head_loss\b"),
    ("optimizer", "scopes", r"\btrain\.(optimizer|clip|state_rule)\b"),
    ("stream", "scopes", r"\battn\.lse\b"),  # the kept log-sum-exp's column, blown back up for the backward kernels
    ("stream", "path", r"/attn\.(full|window)/slice$"),  # and cut out of the forward kernel's lane-broadcast layout
    # the kernels themselves, by the ``name=`` their ``pallas_call`` writes into the path ...
    ("attention_core", "path", r"/attn\.(full|window)/\w+/pallas_call$"),
    # ... and what stands round them under the same scope: the transposes into the kernels' layout, the
    # backward prologue's row sums and lane broadcasts (an XLA attention form, which has no kernel, reads here whole)
    ("attention_glue", "scopes", r"\battn\.(full|window)\b"),
    ("experts", "scopes", r"\bmoe\.experts\b"),
    # XLA:TPU writes its grouped matmul's own op_name, without the stack
    ("experts", "path", r"^ragged-dot-"),
    ("route", "scopes", r"\bmoe\.(route|combine)\b"),
    ("feed_forward", "scopes", r"\bmoe\.shared\b"),
    ("feed_forward", "modules", r"(^|/)block\d+/(gate|up|down)$"),
    ("delta_rule", "scopes", r"\bgdn\.scan\b"),
    ("norms", "scopes", r"\bgdn\.gate_norm\b"),
    ("norms", "modules", r"(^|/)(ln\w*|q_norm|k_norm)$"),
    ("projections", "scopes", r"\b(attn\.gate|gdn\.project)\b"),
    ("projections", "modules", r"(^|/)attn/(query|key|value|out)$"),
    ("mixer_glue", "scopes", r"\bgdn\.conv\b"),
    ("mixer_glue", "modules", r"(^|/)gdn$"),
    ("embed", "modules", r"(^|/)(embed|pos)$"),
    ("head_loss", "modules", r"(^|/)lm_head$"),
    ("route", "modules", r"(^|/)moe$"),  # the expert layer outside its scopes: counts, the auxiliary terms, the windows' branches
    # a weight the compiler lays out anew is named by the argument it is
    ("experts", "path", r"^params\[.*\bmoe\b"),
    ("head_loss", "path", r"^params\[.*\blm_head\b"),
    ("stream", "scopes", r"\blm\.(targets|loss)\b"),
    # what a block or the attention module does itself: residual adds, casts, rotary, splits
    ("stream", "modules", r"(^|/)(block\d+|attn|TransformerLM)$"),
)
_COMPILED = tuple((piece, field, re.compile(rx)) for piece, field, rx in PIECES)


def piece_of(row: Optional[dict]) -> str:
    """The piece of one map row (``modules``, ``scopes``, ``path``)."""
    if not row:
        return UNSCOPED
    fields = {"modules": row.get("modules", ""), "scopes": " ".join(row.get("scopes", ())), "path": row.get("path", "")}
    for piece, field, rx in _COMPILED:
        if rx.search(fields[field]):
            return piece
    return UNSCOPED


def lent(rows: dict, row: Optional[dict]) -> Optional[dict]:
    """The row that classifies an instruction: its own, or, where it has no
    metadata and the map names a neighbour that has (``via``: what uses the copy
    or zero fill the compiler added, else what it reads), that one's."""
    if row and not row.get("path") and row.get("via"):
        return rows.get(row["via"], row)
    return row


def pieces_of(row: Optional[dict]) -> set:
    """The pieces a fusion's fused instructions belong to, ``unscoped`` left
    out: more than one says the fusion mixes pieces."""
    if not row:
        return set()
    found = {piece_of({"modules": m, "scopes": tuple(s)}) for m, s in row.get("fused", ())}
    return found - {UNSCOPED}


def head(name: str) -> str:
    """``fusion.3024`` of ``%fusion.3024 = f32[...] fusion(...)``."""
    return name.partition(" = ")[0].lstrip("%")


def program_map() -> Tuple[Optional[dict], dict]:
    """The step's scope map from the program in this process and what asking
    for it cost (seconds, backend compiles), or None where the program gives
    none."""
    try:
        from heat_tpu.telemetry import CompileWatcher
        from heat_tpu.telemetry.hlo import program_scopes
    except (ImportError, AttributeError):
        return None, {}
    t0 = time.perf_counter()
    with CompileWatcher() as watcher:
        rows = program_scopes(SITE)
    return rows, {"map_request_s": time.perf_counter() - t0, "map_request_compiles": watcher.backend_compiles}


def step_leaves(tr, device) -> Tuple[List[trace_reduce.Event], float]:
    """The leaf events of ``device``'s operations that lie inside a step's
    program in the window, and the programs' own time."""
    lo, hi = tr.window
    programs = sorted(
        (m for m in device.modules if STEP_MODULE.search(m.name) and m.end > lo and m.start < hi),
        key=lambda m: m.start,
    )
    starts = [m.start for m in programs]
    inside = []
    for e in device.ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= programs[i].end:
            inside.append(e)
    return trace_reduce.leaves(inside), sum(m.dur for m in programs)


def table(tr, rows: dict, top: int = 12) -> Optional[dict]:
    """Device time a call, mean over the chips, of the step's leaf events by
    piece and pass (ms), with the totals the note carries."""
    if tr is None or not tr.calls or not tr.devices:
        return None
    per_ms = len(tr.devices) * len(tr.calls) * 1e6
    by: Dict[str, Dict[str, float]] = {}
    unscoped: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    leaf_ns = program_ns = mixed_ns = lent_ns = 0.0
    unmatched = 0
    placed: Dict[str, tuple] = {}  # an instruction runs once a step: classify it once

    def place(name: str) -> tuple:
        own = rows.get(name)
        row = lent(rows, own)
        piece = piece_of(row)
        mixed = len(pieces_of(row) | ({piece} - {UNSCOPED})) > 1
        return own, row, piece, (row or {}).get("pass") or "none", mixed

    for device in tr.devices:
        found, program = step_leaves(tr, device)
        program_ns += program
        for e in found:
            name = head(e.name)
            if name not in placed:
                placed[name] = place(name)
            own, row, piece, which, mixed = placed[name]
            unmatched += own is None
            lent_ns += e.dur if row is not own else 0.0
            cell = by.setdefault(piece, {})
            cell[which] = cell.get(which, 0.0) + e.dur
            leaf_ns += e.dur
            mixed_ns += e.dur if mixed else 0.0
            if piece == UNSCOPED:
                op = (own or {}).get("op", "?")
                key = f"{name} {op} {(row or {}).get('path', '')}"
                unscoped[key] = unscoped.get(key, 0.0) + e.dur
                unscoped_ops[op] = unscoped_ops.get(op, 0.0) + e.dur
    if not leaf_ns:
        return None
    pieces = {p: {k: v / per_ms for k, v in sorted(c.items())} for p, c in sorted(by.items())}
    for c in pieces.values():
        c["all"] = sum(c.values())
    return {
        "pieces_ms": pieces,
        "leaf_ms": leaf_ns / per_ms,
        "program_ms": program_ns / per_ms,
        "recomputed_ms": sum(c.get("recomputed", 0.0) for c in pieces.values()),
        "mixed_share": mixed_ns / leaf_ns,
        "lent_ms": lent_ns / per_ms,  # of instructions without metadata, counted where a neighbour's row puts them
        "unmatched_events": unmatched,
        "unscoped_by_op_ms": {k: v / per_ms for k, v in sorted(unscoped_ops.items(), key=lambda kv: -kv[1])},
        "unscoped_top": [[k, v / per_ms] for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "scopes": sorted({s for r in rows.values() for s in r.get("scopes", ())}),
        "map_rows": len(rows),
    }


def pieces(reading) -> Optional[dict]:
    """The table of this run (kept on the reading and written to
    ``reading.notes['step_scopes']``), or None without a map or a trace."""
    if not hasattr(reading, "_step_scopes"):
        reading._step_scopes = None
        rows, cost = program_map() if reading.trace is not None else (None, {})
        if rows:
            reading._step_scopes = table(reading.trace, rows)
        if reading._step_scopes is not None:
            reading._step_scopes.update(cost)
            reading.notes["step_scopes"] = reading._step_scopes
    return reading._step_scopes


def piece_ms(reading, piece: str) -> Optional[float]:
    """Device time a call of ``piece``, all passes; 0 where the map knows no
    such leaf, None without a map."""
    found = pieces(reading)
    return None if found is None else found["pieces_ms"].get(piece, {}).get("all", 0.0)


def total_ms(reading, key: str) -> Optional[float]:
    found = pieces(reading)
    return None if found is None else found[key]
