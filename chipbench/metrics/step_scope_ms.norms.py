"""Per call: device time of the piece ``norms`` of the train step (``ln*``, ``q_norm``, ``k_norm``,
``ln_f``, ``gdn.gate_norm``), all passes, by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "norms")
