"""Self time of the host path: a call's wall minus the time inside it in
which an operation ran on the device, mean over the calls and the chips."""

from chipbench import trace_reduce as tr_


def read(reading):
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    total = 0.0
    for lo, hi in tr.calls:
        busy = sum(tr_.length(tr_.clip(d.busy, lo, hi)) for d in tr.devices) / len(tr.devices)
        total += (hi - lo) - busy
    return total / len(tr.calls) / 1e6
