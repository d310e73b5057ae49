"""Per call: device time of the looped stack (the scope ``lm.loop``): the four
passes over the blocks and the final norm, forward, backward and recomputed, the
flash kernels in it."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, ouro_trace.LOOP)
