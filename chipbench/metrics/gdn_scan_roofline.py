"""Share of the roofline of the gated delta rule (counts/gdn_scan.py over
``gdn_scan_ms``)."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.share_of_least(reading, qnext_trace.gdn_scan_rx(reading.config), "gdn_scan")
