"""Per call: device time of the full-form flash kernels (``flash_fwd``,
``flash_bwd_*``) of the attention blocks."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.ms_per_call(reading, lfm2_trace.FULL_ATTENTION)
