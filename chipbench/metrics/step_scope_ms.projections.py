"""Per call: device time of the piece ``projections`` of the train step (``attn/query|key|value|out``,
``attn.gate``, ``gdn.project``), all passes, by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "projections")
