"""Per call: device time of the routing around the held experts: the sorts and
what gathers a window's rows and sums them back into their tokens."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.route_rx(reading.config))
