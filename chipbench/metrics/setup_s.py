"""Process start to the first timed call: imports, device, data from the
seed, one warm-up call (which compiles, or loads the compile cache)."""


def read(reading):
    return reading.setup_s
