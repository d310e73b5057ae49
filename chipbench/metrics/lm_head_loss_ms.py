"""Per call: device time of the output head and the cross-entropy, forward
and backward (the two loops over blocks of positions, the backward one with
its recomputed logits)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.ms_per_call(reading, lm_trace.HEAD_LOSS)
