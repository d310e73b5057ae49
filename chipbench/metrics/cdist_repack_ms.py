"""Per call: device-busy time of the distance program outside its
``euclid_tile`` event (the pads before it, the slice after it)."""

from chipbench import program_spans


def read(reading):
    parts = program_spans.around_kernel(reading, program_spans.CDIST_KERNEL)
    if parts is None:
        return None
    reading.notes["cdist_program_ms"] = parts["program"]
    return parts["before"] + parts["between"] + parts["after"]
