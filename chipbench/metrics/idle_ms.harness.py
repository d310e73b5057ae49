"""Per call, mean over the chips: device idle time inside the window under
no span of the program: the kind's own code, the return of
``block_until_ready``, ``chipbench.between_calls`` (the drop of a result)."""

from chipbench import program_spans


def read(reading):
    return program_spans.idle_ms(reading, "harness")
