"""Per call: device time of the AdamW update (the fusions that write a
parameter and its two moments)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.ms_per_call(reading, lm_trace.OPTIMIZER)
