"""Per call: device time of the full-form flash kernels (``flash_fwd``,
``flash_bwd_*``) of the full-attention layers."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.FULL_ATTENTION)
