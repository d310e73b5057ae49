"""Per call: device time of the piece ``feed_forward`` of the train step (a dense block's
``gate|up|down``, ``moe.shared``), all passes, by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "feed_forward")
