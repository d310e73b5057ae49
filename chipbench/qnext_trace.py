"""Readers of the ``qnext_step`` cell's per-layer metrics: the pieces of one
Qwen3-Next training step in the device trace, each found by the name the
compiler gives it or by the shape of what it writes (pinned in
``tests/chipbench/recorded_qnext_step_v5e.txt``), and the routing counters of
``heat_tpu.nn.moe``. The step holds several loops, so a loop is told by what
it carries: the delta rule's scans a state of heads x 128 x 128, the head's
loss a block of logits, the mixers' loop over the sequences neither.

A program without these names or counters (a parent commit) gives ``None``
everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import re

from typing import Optional

from chipbench import program_spans, roofline
from chipbench.lm_trace import ATTENTION, EXPERTS, OPTIMIZER, counter  # noqa: F401

_BEFORE_OPERANDS = r"(?:(?! (?:fusion|custom-call|convolution|copy|sort|scatter)\().)*"


def _loop_carrying(shape: str):
    """A ``while`` whose result tuple holds ``shape``: what a loop carries
    leads its tuple, before what it only reads."""
    return rf"^%while(\.\d+)? = \(.*?{shape}"


def gdn_scan_rx(config: dict):
    """The delta rule, forward and backward: the ``while`` loops whose carry
    holds the state (one sequence x value heads x key size x value size: the
    scan over chunks and its transpose; their events cover their bodies), and,
    outside them, whatever writes an array laid out by chunk: chunks x heads x
    chunk x (chunk | head size), with or without the sequence's axis of one:
    the chunk products, the solve, the decays, the output by chunk."""
    h, dk, dv = config["linear_num_value_heads"], config["linear_key_head_dim"], config["linear_value_head_dim"]
    c = config["delta_chunk"]
    n = -(-config["sequence_length"] // c)
    wide = "|".join(str(x) for x in sorted({c, dk, dv}))
    by_chunk = rf"\[(?:{n},(?:1,)?{h},{c},(?:{wide})|1,{n},{c},{h},{dv})\]"
    return re.compile(
        _loop_carrying(rf"f32\[1,{h},{dk},{dv}\]")
        + rf"|^%(?!while)\S+ = {_BEFORE_OPERANDS}{by_chunk}{_BEFORE_OPERANDS} (?:fusion|convolution|copy)\("
    )


def gdn_mixer_rx(config: dict):
    """Everything the Gated DeltaNet mixers do: their loops over the
    sequences of the batch, forward (twice: every block and every sequence is
    run again in the backward pass) and backward. Such a loop holds the
    convolution's taps (channels x taps) and carries neither a state nor
    logits; its event covers its body, the delta rule's scans among it."""
    channels = 2 * config["linear_num_key_heads"] * config["linear_key_head_dim"] \
        + config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return re.compile(_loop_carrying(rf"f32\[{channels},{config['linear_conv_kernel_dim']}\]"))


def head_loss_rx(config: dict):
    """The blocked cross-entropy's one loop: the ``while`` whose carry holds a
    block of positions x the vocabulary's slice."""
    return re.compile(_loop_carrying(rf"f32\[\d+,{config['vocab_size']}\]"))


def held_rows(config: dict) -> int:
    """Rows of one window of the held experts' work (``nn/moe.py``)."""
    n = config["sequences_per_step"] * config["sequence_length"] * config["num_experts_per_tok"]
    even = -(-n * config["num_experts_held"] // config["num_experts"])
    return min(n, -(-2 * even // 8) * 8)


def route_rx(config: dict):
    """The routing around the held experts: the sorts (top-k and the two by
    expert), the gathers and sums of a window's rows (every fusion, scatter or
    copy whose result is window rows x hidden)."""
    shape = rf"\[{held_rows(config)},{config['hidden_size']}\]"
    return re.compile(
        rf"^%sort(\.\d+)? |^%\S+ = {_BEFORE_OPERANDS}{shape}{_BEFORE_OPERANDS} (?:fusion|scatter|copy)\("
    )


def ms_per_call(reading, rx) -> Optional[float]:
    """Device time a call of the events named ``rx``, mean over the chips. An
    event that lies inside another one of them (an operation of a loop's body
    that is itself named, inside the loop's own event) is not counted again."""
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    total, any_found = 0.0, False
    for device in tr.devices:
        end = -1.0
        for e in sorted(program_spans.kernel_events(tr, device, rx), key=lambda e: (e.start, -e.end)):
            any_found = True
            if e.start >= end:
                total, end = total + e.dur, e.end
    return total / len(tr.devices) / len(tr.calls) / 1e6 if any_found else None


def share_of_least(reading, rx, counts_name: str) -> Optional[float]:
    """Percent: the least time the chip could take for the work that
    ``counts/<counts_name>.py`` counts in one call, over ``ms_per_call``."""
    spent = ms_per_call(reading, rx)
    if not spent:
        return None
    work = reading.parts.module("counts", counts_name).work(reading.config, reading.chips)
    least = roofline.least_seconds(work, reading.peak, reading.chips)
    reading.notes[counts_name + "_roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] * 1e3 / spent
