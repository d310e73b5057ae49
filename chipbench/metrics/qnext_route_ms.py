"""Per call: device time of the routing around the held experts: the sorts and
what gathers a window's rows and sums them back into their tokens."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.route_rx(reading.config))
