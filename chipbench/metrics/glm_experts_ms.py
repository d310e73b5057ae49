"""Per call: device time of the grouped matmuls over the rows that land on the
held experts (``scope_trace``'s piece ``experts``) and of the shared experts
(scope ``moe.shared``), all passes, by the program's scope map."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "experts")
