"""Per call: device time of the tied head and the cross-entropy over the
vocabulary's slice: the one loop whose carry holds a block of logits."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.ms_per_call(reading, lfm2_trace.head_loss_rx(reading.config))
