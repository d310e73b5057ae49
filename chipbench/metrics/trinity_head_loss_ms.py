"""Per call: device time of the output head and the cross-entropy over the
vocabulary's slice: the one loop whose carry holds a block of logits."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.head_loss_rx(reading.config))
