"""Per call: device time of the expert layers' grouped matmuls, forward and
backward (the nine ``ragged-dot`` kernels of a layer)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.ms_per_call(reading, lm_trace.EXPERTS)
