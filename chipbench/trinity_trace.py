"""Readers of the ``trinity_step`` cell's per-layer metrics. What the other
two training cells already read (the grouped matmuls, AdamW's fusions, the
routing around held experts, the head's loop, the full-form flash kernels, the
counters) is read by ``lm_trace`` and ``qnext_trace``; this file adds the
window-form kernels, told from the full form by the ``name=`` of their
``pallas_call`` (pinned in ``tests/chipbench/recorded_trinity_step_v5e.txt``).

A program without these names or counters (a parent commit) gives ``None``
everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re

from chipbench.qnext_trace import (  # noqa: F401  (the metrics' files read these from here)
    _BEFORE_OPERANDS, ATTENTION as FULL_ATTENTION, EXPERTS, OPTIMIZER, counter, head_loss_rx, ms_per_call,
    share_of_least,
)

# ``name=`` of the windowed attention ``pallas_call``s (forward, dq, dk/dv; the fused backward)
WINDOW_ATTENTION = re.compile(r"^%swa_(fwd|bwd_[a-z]+)(\.\d+)? ")


def window_rows(config: dict):
    """Rows of the first window of the held experts' work and of a further one
    (``heat_tpu/nn/moe.py::_held_experts``: 2 x an even share of the step's
    assignments, then one even share at a time)."""
    n = config["sequences_per_step"] * config["sequence_length"] * config["num_experts_per_tok"]
    even = -(-n * config["num_experts_held"] // config["num_experts"])
    first = min(n, -(-2 * even // 8) * 8)
    return first, min(n - first, -(-even // 8) * 8)


def route_rx(config: dict):
    """The routing around the held experts: the sorts (top-k and the two by
    expert) and every fusion, scatter or copy that writes window rows x hidden
    or tokens x hidden *by an int32 index vector of the window's length* (the
    gathers of a window's rows by their tokens, the sums back into the tokens,
    their transposes). The shape alone, which
    ``qnext_trace.route_rx`` goes by, is not enough where a window is as long as
    the sequence (it is here: 2 x 131,072 / 16 = 16,384, and
    every norm and residual pass over the stream read as routing, 161.8 ms of a
    1,098 ms step, my chip run, PR 32, call 3); the embedding's gather takes an
    index vector too and is told by its table."""
    (first, further), tokens = window_rows(config), config["sequences_per_step"] * config["sequence_length"]
    rows = f"(?:{first}|{further})"
    shape = rf"\[(?:{rows}|{tokens}),{config['hidden_size']}\]"  # a window's rows gathered, or summed back into the tokens
    table = rf"\[{config['vocab_size']},{config['hidden_size']}\]"
    return re.compile(
        rf"^%sort(\.\d+)? |^%\S+ = {_BEFORE_OPERANDS}{shape}{_BEFORE_OPERANDS} (?:fusion|scatter|copy)\((?!.*{table}).*s32\[{rows}[\],]"
    )


def blocks_visited_over_live():
    """Key blocks that the window kernels' grids visit over those that hold a
    pair some query sees, from the counters the program keeps of its static
    grids: 1.0 is a grid that covers the band and no more."""
    visited, live = counter("attn.window.blocks_visited"), counter("attn.window.blocks_live")
    return visited / live if visited is not None and live else None
