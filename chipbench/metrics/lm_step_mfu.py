"""Model FLOP/s utilization of the training step while its program runs: the
FLOPs of counts/olmoe_step.py (no recomputation, causal attention once) at
the peak, over the device time of the step's programs."""

from chipbench import roofline


def read(reading):
    return roofline.share(reading, "olmoe_step")
