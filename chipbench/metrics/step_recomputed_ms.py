"""Per call: device time of every leaf of the train step whose pass is ``recomputed`` (a
checkpoint's rematerialised computation, run again in the backward pass): what rematerialisation costs."""

from chipbench import scope_trace


def read(reading):
    return scope_trace.total_ms(reading, "recomputed_ms")
