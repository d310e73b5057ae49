"""Model FLOP/s utilization of the Qwen3-Next training step while its program
runs: the FLOPs of counts/qnext_step.py (no recomputation, causal attention
once, the held experts at an even routing) at the peak, over the device time of
the step's programs."""

from chipbench import roofline


def read(reading):
    return roofline.share(reading, "qnext_step")
