"""Per call, mean over the chips: device idle time under any ``*.prepare``
span of the program (host data and operands to placed, laid-out buffers)."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "prepare")
