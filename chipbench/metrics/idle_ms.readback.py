"""Per call, mean over the chips: device idle time under any ``*.readback``
span of the program (the host reads a number back from the device and waits for it)."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "readback")
