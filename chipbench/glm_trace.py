"""Readers of the ``glm_step`` cell's per-layer metrics. What the other
training cells already read by the operations' own names (AdamW's fusions, the
head's loops, the full-form flash kernels, the counters) is read through
``lfm2_trace`` and ``trinity_trace``; this file adds what is found by the
program's own scope map (``heat_tpu.telemetry.hlo.program_scopes`` joined to the
device trace, as ``scope_trace`` joins it): every leaf event of the step gets
the tags of :func:`tags_of`, which overlap (a projection of the module's mixer
is ``latent``, ``latent_proj`` and ``mtp``), and the shares that take this
configuration's own counts (the whole step at the run's held load; the flash
kernels at heads of 256 on 20 heads).

A program without these names or counters (a parent commit) gives ``None``
everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re
from typing import Optional

from chipbench import roofline, scope_trace
from chipbench.lfm2_trace import (  # noqa: F401  (the metrics' files read these from here)
    FULL_ATTENTION, OPTIMIZER, _rows, counter, head_loss_rx, ms_per_call, share_of,
)

_LATENT_MODULE = re.compile(r"(^|/)block\d+/attn(/|$)")  # everything a latent mixer's module does, its kernels too
_DENSE_MODULE = re.compile(r"(^|/)block\d+/(gate|up|down)$")  # the leading block's SwiGLU
_SCOPES = {
    "latent_proj": re.compile(r"\bmla\.(down|up)\b"),
    "latent_assemble": re.compile(r"\bmla\.assemble\b"),
    "mtp": re.compile(r"\bmtp\.(merge|block|head_loss)\b"),
    "shared": re.compile(r"\bmoe\.shared\b"),
}


def tags_of(row: Optional[dict]) -> set:
    """What one map row counts into. ``latent_assemble`` is read from the
    row's own scopes and from those of what is fused into it: the joins and
    rotary are elementwise, XLA fuses them into the products beside them, and a
    scope read from the fusion's root alone misses most of their time."""
    if not row:
        return set()
    scopes = " ".join(row.get("scopes", ()))
    tags = {tag for tag, rx in _SCOPES.items() if rx.search(scopes)}
    if any(_SCOPES["latent_assemble"].search(" ".join(s)) for _, s in row.get("fused", ())):
        tags.add("latent_assemble")
    modules = row.get("modules", "")
    if _LATENT_MODULE.search(modules):
        tags.add("latent")
    if _DENSE_MODULE.search(modules):
        tags.add("dense_ffn")
    piece = scope_trace.piece_of(row)
    if piece == "route":
        tags.add("route")
    if piece == "experts" or "shared" in tags:
        tags.add("experts")
    return tags


def tagged(reading) -> Optional[dict]:
    """Device time a call (ms, mean over the chips) of the step's leaf events
    by tag, all passes; joined once a reading and left in the note
    ``glm_tags``. An instruction without metadata goes where
    ``scope_trace.lent`` puts it. None without a trace, a map, a leaf the map
    names, or one of the scopes this PR's program has (a parent's program)."""
    if not hasattr(reading, "_glm_tags"):
        reading._glm_tags = None
        tr, rows = reading.trace, _rows(reading)
        rows = rows if tr is not None and tr.calls else None
        by, placed = {}, {}
        for device in tr.devices if rows else ():
            for e in scope_trace.step_leaves(tr, device)[0]:
                name = scope_trace.head(e.name)
                if name not in placed:
                    placed[name] = tags_of(scope_trace.lent(rows, rows.get(name)))
                for tag in placed[name]:
                    by[tag] = by.get(tag, 0.0) + e.dur
        if "latent_proj" in by:
            per_ms = len(tr.devices) * len(tr.calls) * 1e6
            reading._glm_tags = reading.notes["glm_tags"] = {k: v / per_ms for k, v in sorted(by.items())}
    return reading._glm_tags


def tag_ms(reading, tag: str) -> Optional[float]:
    found = tagged(reading)
    return None if found is None else found.get(tag)


def held_share(reading) -> Optional[float]:
    """The share of a step's assignments that land on the held experts, over
    an even share, mean over the steps the process made and over the expert
    layers, the module's among them: what the held experts' rows are counted at."""
    total, steps = counter("moe.held_share"), counter("moe.steps")
    if total is None or not steps or "n_routed_experts" not in reading.config:
        return None
    return total / steps * reading.config["n_routed_experts"] / reading.config["num_experts_held"]


def held_load(reading) -> Optional[float]:
    """The busiest held expert's rows over an even share of a layer's
    assignments (all of them over all experts), the worst of the expert layers
    (the module's among them), mean over the window's calls, from the counts
    that each call's step returned."""
    c = reading.config
    if "n_routed_experts" not in c:
        return None
    first, held = c["first_expert_held"], c["num_experts_held"]
    worst = []
    for call in reading.window.calls:
        counts = (call.summary or {}).get("expert_counts") if call.error is None else None
        if counts is not None:
            even = counts.sum(-1) / c["n_routed_experts"]
            worst.append(float((counts[:, first:first + held].max(-1) / even).max()))
    return sum(worst) / len(worst) if worst else None


def step_mfu(reading) -> Optional[float]:
    """``roofline.share`` with the step's count taken at the run's held load."""
    tr = reading.trace
    if tr is None or not tr.calls or counter("mla.mixers") is None:
        return None
    spent = tr.module_time(reading.config["roofline_modules"]) / 1e9 / len(tr.devices)
    load = held_share(reading)
    if spent <= 0 or load is None:
        return None
    work = reading.parts.module("counts", "glm_step").work(reading.config, reading.chips, load)
    reading.notes["glm_step_held_share"] = load
    return 100.0 * roofline.least_seconds(work, reading.peak, reading.chips)["seconds"] * len(tr.calls) / spent


def attention_ms(reading) -> Optional[float]:
    return ms_per_call(reading, FULL_ATTENTION) if counter("mla.mixers") is not None else None


def attention_roofline(reading) -> Optional[float]:
    spent = attention_ms(reading)
    if not spent:
        return None
    built = counter("mla.key_rows_built")
    if built is not None:
        reading.notes["mla_key_rows_built"] = built  # summed over the mixers the process traced
    work = reading.parts.module("counts", "glm_step").attention_work(reading.config, reading.chips)
    return share_of(reading, spent, work, "glm_attention")
