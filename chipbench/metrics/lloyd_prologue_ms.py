"""Per call: device-busy time of the fit's program before its first
``lloyd_update`` event (the layout copy of X)."""

from chipbench import program_spans


def read(reading):
    parts = program_spans.around_kernel(reading, program_spans.LLOYD_KERNEL)
    if parts is None:
        return None
    reading.notes["lloyd_program_ms"] = parts["program"]
    reading.notes["lloyd_between_kernels_ms"] = parts["between"]
    return parts["before"]
