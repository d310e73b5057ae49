"""Per call, mean over the chips: device idle time under any ``*.launch``
span of the program (the call into the jitted program: the enqueue)."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "launch")
