"""Per call: device time of the piece ``stream`` of the train step (residual adds, casts, rotary, what a
block or the attention module does itself; the kept log-sum-exp's slices and broadcasts), all passes,
by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "stream")
