"""Per call: device time of the gated delta rule, forward and backward (with
the forward passes the backward pass runs again): the scans over chunks and
the chunk-by-chunk products, solves and decays around them."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.gdn_scan_rx(reading.config))
