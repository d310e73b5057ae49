"""Median wall of one call in the window, host clock, from the call to
``block_until_ready`` on every output the user reads."""

import statistics


def read(reading):
    return statistics.median(c.ms for c in reading.window.calls)
