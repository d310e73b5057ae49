"""Per call: device time of what rematerialisation runs a second time (the
scope map's pass ``recomputed``), whatever its piece: it lies inside the pieces'
own times and is no piece beside them."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, lfm2_trace.RECOMPUTED)
