"""Per call: device time of the piece ``mixer_glue`` of the train step (``gdn.conv`` and what else lies
under ``gdn`` outside its other scopes: the mixer's re-layouts, its output projection), all passes,
by the program's scope map."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.piece_ms(reading, "mixer_glue")
