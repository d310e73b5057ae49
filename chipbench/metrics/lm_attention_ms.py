"""Per call: device time of the flash attention kernels, forward and
backward, by their names (``flash_fwd``, ``flash_bwd_*``)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.ms_per_call(reading, lm_trace.ATTENTION)
