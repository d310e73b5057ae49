"""Per call: device time of AdamW's update fusions (a parameter and its two
moments written together)."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.OPTIMIZER)
