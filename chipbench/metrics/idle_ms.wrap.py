"""Per call, mean over the chips: device idle time under any ``*.wrap``
span of the program (result objects, and a program they may start)."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "wrap")
