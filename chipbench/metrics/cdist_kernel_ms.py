"""Per call: device time of the ``euclid_tile`` kernel events."""

from chipbench import program_spans


def read(reading):
    return program_spans.kernel_ms(reading, program_spans.CDIST_KERNEL, "call")
