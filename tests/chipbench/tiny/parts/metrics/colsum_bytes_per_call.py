def read(reading):
    return float(reading.parts.module("counts", "colsum").work(reading.config, reading.chips)["bytes"])
