"""Per call: device time of the output head and the cross-entropy over the
vocabulary's slice: the one loop whose carry holds a block of logits (the
mixers' loops carry none)."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.head_loss_rx(reading.config))
