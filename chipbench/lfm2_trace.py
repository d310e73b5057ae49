"""Readers of the ``lfm2_step`` cell's per-layer metrics. What the other
training cells already read by the operations' own names (the grouped matmuls,
AdamW's fusions, the head's loop, the full-form flash kernels, the counters) is
read by ``lm_trace``, ``qnext_trace`` and ``trinity_trace``; this file adds what
is found by the program's own scope map
(``heat_tpu.telemetry.hlo.program_scopes`` joined to the device trace, as
``scope_trace`` joins it): ``scope_trace``'s table of pieces with one row before
it, the short-convolution mixers by their flax module ``block<i>/conv`` (in
``scope_trace.PIECES`` they would read ``unscoped``), so that every leaf event
of the step lies in one piece and no other; and the shares that take this
configuration's own counts (the whole step at the run's held load; the flash
kernels at heads of 64; the mixers' products).

A program without these names or counters (a parent commit) gives ``None``
everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re
from typing import Optional

from chipbench import roofline, scope_trace
from chipbench.trinity_trace import (  # noqa: F401  (the metrics' files read these from here)
    EXPERTS, FULL_ATTENTION, OPTIMIZER, counter, head_loss_rx, ms_per_call,
)

CONV_MIXER = "conv_mixer"
_CONV_MODULE = re.compile(r"(^|/)block\d+/conv$")  # everything the mixers' modules do: projections, gates, taps
RECOMPUTED = "pass:recomputed"


def piece_of(row: Optional[dict]) -> str:
    """``scope_trace.piece_of`` behind the mixers' own row."""
    if row and _CONV_MODULE.search(row.get("modules", "")):
        return CONV_MIXER
    return scope_trace.piece_of(row)


def _rows(reading) -> Optional[dict]:
    """The step's scope map, asked for once a reading."""
    if not hasattr(reading, "_lfm2_rows"):
        reading._lfm2_rows = scope_trace.program_map()[0] if reading.trace is not None else None
    return reading._lfm2_rows


def pieces(reading) -> Optional[dict]:
    """Device time a call (ms, mean over the chips) of the step's leaf events
    by piece, all passes, and by pass (``pass:<name>``: ``pass:recomputed`` is
    what rematerialisation runs again, whatever its piece); joined once a
    reading and left in the note ``lfm2_pieces``. An instruction without
    metadata goes where ``scope_trace.lent`` puts it. None without a trace, a
    map, or a leaf the map names."""
    if not hasattr(reading, "_lfm2_pieces"):
        reading._lfm2_pieces = None
        tr, rows = reading.trace, _rows(reading)
        rows = rows if tr is not None and tr.calls else None
        by = {}
        for device in tr.devices if rows else ():
            for e in scope_trace.step_leaves(tr, device)[0]:
                own = rows.get(scope_trace.head(e.name))
                row = scope_trace.lent(rows, own)
                for key in (piece_of(row), "pass:" + ((row or {}).get("pass") or "none")):
                    by[key] = by.get(key, 0.0) + e.dur
        if by:
            per_ms = len(tr.devices) * len(tr.calls) * 1e6
            reading._lfm2_pieces = reading.notes["lfm2_pieces"] = {k: v / per_ms for k, v in sorted(by.items())}
    return reading._lfm2_pieces


def piece_ms(reading, piece: str) -> Optional[float]:
    found = pieces(reading)
    return None if found is None else found.get(piece)


def share_of(reading, spent_ms: Optional[float], work: dict, note: str) -> Optional[float]:
    """Percent: the least time the chip could take for ``work`` in one call,
    over ``spent_ms``."""
    if not spent_ms:
        return None
    least = roofline.least_seconds(work, reading.peak, reading.chips)
    reading.notes[note + "_roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] * 1e3 / spent_ms


def held_load(reading) -> Optional[float]:
    """The share of a step's assignments that land on the held experts, over
    an even share, mean over the steps the process made."""
    total, steps = counter("moe.held_share"), counter("moe.steps")
    if total is None or not steps:
        return None
    return total / steps * reading.config["num_experts"] / reading.config["num_experts_held"]


def step_mfu(reading) -> Optional[float]:
    """``roofline.share`` with the step's count taken at the run's held load."""
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    spent = tr.module_time(reading.config["roofline_modules"]) / 1e9 / len(tr.devices)
    load = held_load(reading)
    if spent <= 0 or load is None:
        return None
    work = reading.parts.module("counts", "lfm2_step").work(reading.config, reading.chips, load)
    reading.notes["lfm2_step_held_load"] = load
    return 100.0 * roofline.least_seconds(work, reading.peak, reading.chips)["seconds"] * len(tr.calls) / spent


def conv_mixer_roofline(reading) -> Optional[float]:
    work = reading.parts.module("counts", "lfm2_step").conv_mixer_work(reading.config, reading.chips)
    return share_of(reading, piece_ms(reading, CONV_MIXER), work, "lfm2_conv_mixer")


def attention_roofline(reading) -> Optional[float]:
    spent = ms_per_call(reading, FULL_ATTENTION)
    if not spent:
        return None
    lanes = counter("attn.lanes_padded")
    if lanes is not None:
        reading.notes["attn_lanes_padded"] = lanes  # summed over the kernel calls the process traced
    work = reading.parts.module("counts", "lfm2_step").attention_work(reading.config, reading.chips)
    return share_of(reading, spent, work, "lfm2_attention")
