"""Per call: device time of the leading dense block's SwiGLU (its ``gate``,
``up`` and ``down`` modules), all passes, by the program's scope map
(``scope_trace``'s piece ``feed_forward``)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "feed_forward")
