"""95th percentile of the calls' walls in the window (of all calls: those
that failed count with the time they took)."""

import numpy as np


def read(reading):
    return float(np.percentile([c.ms for c in reading.window.calls], 95))
