"""Mean device time of one ``lloyd_update`` kernel event, over the chips."""

from chipbench import program_spans


def read(reading):
    return program_spans.kernel_ms(reading, program_spans.LLOYD_KERNEL, "event")
