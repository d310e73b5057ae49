"""Per call: device time of what a block does itself in the looped stack: residual adds, casts, rotary, the kept log-sum-exp,
all passes, by the program's scope map: the piece ``stream`` of ``scope_trace.PIECES`` inside the scope
``lm.loop``. A fusion is counted whole under its root's piece (``PERF.md`` section 7)."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, "loop:stream")
