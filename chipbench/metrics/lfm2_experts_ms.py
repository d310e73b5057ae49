"""Per call: device time of the grouped matmuls over the rows that land on the
held experts (XLA:TPU's ``ragged-dot``)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.ms_per_call(reading, lfm2_trace.EXPERTS)
