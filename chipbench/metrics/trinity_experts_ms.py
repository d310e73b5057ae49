"""Per call: device time of the grouped matmuls over the rows that land on the
held experts (XLA:TPU's ``ragged-dot``)."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.EXPERTS)
