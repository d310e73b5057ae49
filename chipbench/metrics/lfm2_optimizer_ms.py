"""Per call: device time of AdamW's update fusions (a parameter and its two
moments written together)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.ms_per_call(reading, lfm2_trace.OPTIMIZER)
