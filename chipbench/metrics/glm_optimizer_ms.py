"""Per call: device time of AdamW's update fusions (a parameter and its two
moments written together)."""

from chipbench import glm_trace


def read(reading):
    if glm_trace.counter("mla.mixers") is None:
        return None
    return glm_trace.ms_per_call(reading, glm_trace.OPTIMIZER)
