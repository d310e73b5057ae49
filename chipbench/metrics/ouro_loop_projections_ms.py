"""Per call: device time of the four attention projections of the looped stack (``attn/query|key|value|out``),
all passes, by the program's scope map: the piece ``projections`` of ``scope_trace.PIECES`` inside the scope
``lm.loop``. A fusion is counted whole under its root's piece (``PERF.md`` section 7)."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, "loop:projections")
