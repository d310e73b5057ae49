"""Per call: device time of AdamW's update fusions (a parameter and its two
moments written together)."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.OPTIMIZER)
