"""Per call: device time of the piece ``embed`` of the train step (the embedding and its gradient),
all passes, by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "embed")
