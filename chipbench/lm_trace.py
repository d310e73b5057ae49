"""Readers of the ``lm_step`` cell's per-layer metrics: the pieces of one
training step in the device trace, each found by the name the compiler
gives it (pinned in ``tests/chipbench/recorded_lm_step_v5e.txt``), and the
routing counters of ``heat_tpu.nn.moe``.

A program without these kernel names or counters (a parent commit) gives
``None`` everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re
from typing import Optional

from chipbench import program_spans, roofline

# XLA:TPU's own grouped matmul, what ``jax.lax.ragged_dot`` lowers to
EXPERTS = re.compile(r"^%ragged-dot-none(\.\d+)? ")
# ``name=`` of the attention ``pallas_call``s
ATTENTION = re.compile(r"^%flash_(fwd|bwd_[a-z]+)(\.\d+)? ")
# the blocked cross-entropy: the only loops of the step (``lax.map`` forward,
# its transpose backward); a ``while`` event covers the operations of its body
HEAD_LOSS = re.compile(r"^%while(\.\d+)? ")
# AdamW: a fusion that writes a parameter and its two moments, three float32
# arrays of one shape
OPTIMIZER = re.compile(r"^%\S+ = \((f32\[[\d,]+\])\{[^}]*\}, \1\{[^}]*\}, \1\{[^}]*\}\) fusion\(")


def route_rx(config: dict):
    """The routing around the experts: the sorts (top-k and the two by
    expert), and every fusion whose result is an array of assignments x
    hidden: the gathers by the sorted order and by its inverse, their
    transposes and the casts fused into them."""
    rows = config["sequences_per_step"] * config["sequence_length"] * config["num_experts_per_tok"]
    shape = rf"\[{rows},{config['hidden_size']}\]"
    before_operands = r"(?:(?! fusion\().)*"
    return re.compile(rf"^%sort(\.\d+)? |^%\S+ = {before_operands}{shape}{before_operands} fusion\(")


def ms_per_call(reading, rx) -> Optional[float]:
    """Device time a call of the events named ``rx``, mean over the chips."""
    return program_spans.kernel_ms(reading, rx, "call")


def share_of_least(reading, rx, counts_name: str) -> Optional[float]:
    """Percent: the least time the chip could take for the work that
    ``counts/<counts_name>.py`` counts in one call, over the device time of
    the events named ``rx`` in one call."""
    spent = ms_per_call(reading, rx)
    if not spent:
        return None
    work = reading.parts.module("counts", counts_name).work(reading.config, reading.chips)
    least = roofline.least_seconds(work, reading.peak, reading.chips)
    reading.notes[counts_name + "_roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] * 1e3 / spent


def counter(name: str) -> Optional[float]:
    """A counter of the program's telemetry registry, None where it was
    never counted."""
    try:
        from heat_tpu import telemetry

        counters = telemetry.get_registry().counters
    except (ImportError, AttributeError):
        return None
    return counters[name] if name in counters else None
