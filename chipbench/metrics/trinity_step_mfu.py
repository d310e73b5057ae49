"""Model FLOP/s utilization of the Trinity-Mini training step while its program
runs: the FLOPs of counts/trinity_step.py (no recomputation, the band counted
as a band, full attention once, the held experts at an even routing) at the
peak, over the device time of the step's programs."""

from chipbench import roofline


def read(reading):
    return roofline.share(reading, "trinity_step")
