"""Share of the roofline of the AdamW update: 28 bytes a parameter
(counts/adamw.py) at the peak bandwidth, over ``lm_optimizer_ms``."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.share_of_least(reading, lm_trace.OPTIMIZER, "adamw")
