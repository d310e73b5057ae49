"""Per call: device time of what a block does to the residual stream itself
(adds, casts, rotary, the kept log-sum-exp's column), all passes, by the
program's scope map (``scope_trace``'s piece ``stream``)."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "stream")
