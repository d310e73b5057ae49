"""Per call: device time of the attention blocks' four projections (query,
key, value, out), all passes, by the program's scope map (``scope_trace``'s
piece ``projections``)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "projections")
