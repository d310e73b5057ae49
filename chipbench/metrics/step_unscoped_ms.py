"""Per call: device time of the train step's leaf events that the program's scope map gives
no piece: no row, a row without metadata, or a path no row of ``scope_trace.PIECES`` names. The coverage of the map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "unscoped")
