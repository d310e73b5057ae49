"""Per call: device time of the head and the cross-entropy over the
vocabulary's slice: the two loops whose carry holds a block of logits, the
trunk's and the module's."""

from chipbench import glm_trace


def read(reading):
    if glm_trace.counter("lm.mtp.modules") is None:
        return None
    return glm_trace.ms_per_call(reading, glm_trace.head_loss_rx(reading.config))
