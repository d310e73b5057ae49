"""Device programs started per call, from the trace's line of programs, on
the chip that starts most (a program over several chips is one launch; the
small ones around it run on the first chip alone)."""


def read(reading):
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    lo, hi = tr.window
    started = max(sum(1 for e in d.modules if lo <= e.start < hi) for d in tr.devices)
    return started / len(tr.calls)
