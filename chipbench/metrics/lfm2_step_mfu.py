"""Model FLOP/s utilization of the LFM2 training step while its program runs:
the FLOPs of counts/lfm2_step.py (no recomputation, heads of 64, full attention
once, the held experts' rows as the run's counters saw them routed) at the peak,
over the device time of the step's programs."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.step_mfu(reading)
