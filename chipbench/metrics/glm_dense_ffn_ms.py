"""Per call: device time of the leading dense block's SwiGLU (its ``gate``,
``up`` and ``down`` modules), all passes, by the program's scope map."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "dense_ffn")
