"""Per call: device time under the scopes ``mtp.merge``, ``mtp.block`` and
``mtp.head_loss``, all passes: what the second token costs a step."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "mtp")
