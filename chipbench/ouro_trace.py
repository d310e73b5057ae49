"""Readers of the ``ouro_step`` cell's per-layer metrics. The flash kernels and
the counters are read as the other training cells read them (``trinity_trace``);
the rest is found by the program's own scope map
(``heat_tpu.telemetry.hlo.program_scopes`` joined to the device trace, as
``scope_trace`` joins it), with this kind's own rows first: the head's loop
(``lm.head_loss``), the exit gate with the exit distribution and its entropy
(``lm.exit_gate``), the optimizer, and **the looped stack** (``lm.loop``:
everything the ``total_ut_steps`` passes over the blocks and the final norm run,
forward, backward and recomputed, the flash kernels in it, and what the
compiler lifts out of the loop: the blocks' weight copies, rotary's tables), so
that every leaf event of the step lies in one piece and no other. The loop's
leaves are cut once more by ``scope_trace.PIECES`` (``loop:<piece>``), and how
often the step runs a block body it holds is read off the device trace and the
compiled step's call sites (:func:`loop_passes`), not off a field of the model.

A program without these names or counters (a parent commit) gives ``None``
everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re
from typing import Optional

from chipbench import program_spans, roofline, scope_trace
from chipbench.lfm2_trace import RECOMPUTED, share_of  # noqa: F401  (the metrics' files read these from here)
from chipbench.trinity_trace import FULL_ATTENTION, counter, ms_per_call  # noqa: F401

HEAD_LOSS, EXIT_GATE, OPTIMIZER, LOOP = "head_loss", "exit_gate", "optimizer", "loop"
_OWN = (
    (HEAD_LOSS, re.compile(r"\blm\.head_loss\b")), (EXIT_GATE, re.compile(r"\blm\.exit_gate\b")),
    (OPTIMIZER, re.compile(r"\btrain\.(optimizer|clip|state_rule)\b")), (LOOP, re.compile(r"\blm\.loop\b")),
)


FLASH_FORWARD = re.compile(r"^%?flash_fwd(\.\d+)?( |$)")  # the forward kernel: a device event's line, or its instruction's name
_HOISTED = re.compile(r"(^|/)block\d+(/|$)|^params\[.*\bblock\d+\b")  # a block's module, or a block's weight laid out anew


def piece_of(row: Optional[dict]) -> str:
    """This kind's rows, then what the compiler lifted out of the loop (the
    blocks' bfloat16 weight copies, rotary's tables: a block's module or
    parameter without the loop's scope), then ``scope_trace.piece_of``."""
    row = row or {}
    scopes = " ".join(row.get("scopes", ()))
    for piece, rx in _OWN:
        if rx.search(scopes):
            return piece
    if _HOISTED.search(row.get("modules", "")) or _HOISTED.search(row.get("path", "")):
        return LOOP
    return scope_trace.piece_of(row)


def _rows(reading) -> Optional[dict]:
    """The scope map of the step this process ran, where a traced window ran a
    looped program (the counter ``lm.loop.passes``)."""
    tr = reading.trace
    return scope_trace.program_map()[0] if tr is not None and tr.calls and counter("lm.loop.passes") else None


def pieces(reading) -> Optional[dict]:
    """Device time a call (ms, mean over the chips) of the step's leaf events
    by piece, all passes, by pass (``pass:<name>``), and of the loop's by
    ``scope_trace.PIECES`` (``loop:<piece>``: projections, feed_forward, norms,
    stream, the attention kernels and what stands round them; ``loop:hoisted``
    is what that table does not name: the weight copies lifted out of the
    loop); joined once a reading
    and left in the note ``ouro_pieces``. None without a trace, a looped
    program (the counter ``lm.loop.passes``), a map, or a leaf the map names."""
    if not hasattr(reading, "_ouro_pieces"):
        reading._ouro_pieces = None
        tr = reading.trace
        rows = _rows(reading)
        by = {}
        for device in tr.devices if rows else ():
            for e in scope_trace.step_leaves(tr, device)[0]:
                row = scope_trace.lent(rows, rows.get(scope_trace.head(e.name)))
                piece = piece_of(row)
                keys = [piece, "pass:" + ((row or {}).get("pass") or "none")]
                if piece == LOOP:  # the loop cut once more, by the table the other training cells' steps are cut by
                    inside = scope_trace.piece_of(row)
                    keys.append("loop:" + ("hoisted" if inside == scope_trace.UNSCOPED else inside))
                for key in keys:
                    by[key] = by.get(key, 0.0) + e.dur
        if by:
            per_ms = len(tr.devices) * len(tr.calls) * 1e6
            reading._ouro_pieces = reading.notes["ouro_pieces"] = {k: v / per_ms for k, v in sorted(by.items())}
    return reading._ouro_pieces


def piece_ms(reading, piece: str) -> Optional[float]:
    found = pieces(reading)
    return None if found is None else found.get(piece, 0.0)


def loop_passes(reading) -> Optional[float]:
    """How often the step runs a block body it holds, read off the program the
    window ran: the flash forward kernels a call in the device trace (a block
    application runs one; the backward pass keeps its result and runs none
    again) over the forward kernel's call sites in the compiled step's scope
    map. ``total_ut_steps`` for the loop, 1 for a stack written out or a loop
    unrolled, whatever the model's fields say."""
    sites = sum(1 for name in _rows(reading) or () if FLASH_FORWARD.match(name))
    ran = program_spans.kernel_events_per_call(reading, FLASH_FORWARD)
    return ran / sites if ran and sites else None


def step_mfu(reading) -> Optional[float]:
    return roofline.share(reading, "ouro_step") if counter("lm.loop.passes") else None


def attention_ms(reading) -> Optional[float]:
    return ms_per_call(reading, FULL_ATTENTION) if counter("lm.loop.passes") else None


def attention_roofline(reading) -> Optional[float]:
    spent = attention_ms(reading)
    if not spent:
        return None
    work = reading.parts.module("counts", "ouro_step").attention_work(reading.config, reading.chips)
    return share_of(reading, spent, work, "ouro_attention")
