"""Per call: device time of the norms (two a block, the head norms, the
final one), all passes, by the program's scope map (``scope_trace``'s piece
``norms``)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "norms")
