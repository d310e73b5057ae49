"""Per call: device time of the grouped matmuls over the rows that land on the
held experts (XLA:TPU's ``ragged-dot``)."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.EXPERTS)
