"""Per call: device time of the SwiGLU's three products of the looped stack (``block<i>/gate|up|down``),
all passes, by the program's scope map: the piece ``feed_forward`` of ``scope_trace.PIECES`` inside the scope
``lm.loop``. A fusion is counted whole under its root's piece (``PERF.md`` section 7)."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, "loop:feed_forward")
