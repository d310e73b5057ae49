"""Per call: device time of the Gated DeltaNet mixers as a whole: projections,
convolution, gates, the delta rule (``gdn_scan_ms`` is inside this), the gated
norm and the output projection, forward (with the two passes the backward pass
runs again) and backward: the loops over the batch's sequences."""

from chipbench import qnext_trace


def read(reading):
    return qnext_trace.ms_per_call(reading, qnext_trace.gdn_mixer_rx(reading.config))
