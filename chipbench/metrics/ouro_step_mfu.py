"""Model FLOP/s utilization of the Ouro training step while its program runs:
the FLOPs of counts/ouro_step.py (every pass through the blocks and every exit
through the head counted, recomputation not) at the peak, over the device time
of the step's programs."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.step_mfu(reading)
