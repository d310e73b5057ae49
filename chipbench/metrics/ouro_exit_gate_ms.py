"""Per call: device time of the exit gate, the exit distribution and its
entropy (the scope ``lm.exit_gate``), forward and backward."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, ouro_trace.EXIT_GATE)
