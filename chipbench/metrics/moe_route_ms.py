"""Per call: device time of the routing around the experts: the sorts (top-k
and the two by expert) and the fusions that move the assignments x hidden
rows (the gathers by the sorted order and its inverse, their transposes)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.ms_per_call(reading, lm_trace.route_rx(reading.config))
