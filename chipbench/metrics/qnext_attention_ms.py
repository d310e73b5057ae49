"""Per call: device time of the flash attention kernels (forward, dq, dk/dv:
16 query heads on 2 key-value heads of 256)."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.ATTENTION)
