"""Per call: device idle time under the step's ``heat_tpu.train.step.readback``
span."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "readback")
