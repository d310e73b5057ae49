"""Per call: device-busy time of the fit's program after its last
``lloyd_update`` event (labels and inertia)."""

from chipbench import program_spans


def read(reading):
    parts = program_spans.around_kernel(reading, program_spans.LLOYD_KERNEL)
    return None if parts is None else parts["after"]
