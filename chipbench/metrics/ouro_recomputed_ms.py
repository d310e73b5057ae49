"""Per call: device time of what rematerialisation runs a second time (the
scope map's pass ``recomputed``): every block application's forward but its
flash kernel. It lies inside ``ouro_loop_ms`` and is no piece beside it."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, ouro_trace.RECOMPUTED)
