"""Model FLOP/s utilization of the GLM-4.7-Flash training step while its program
runs: the FLOPs of counts/glm_step.py (no recomputation, latent projections,
heads of 256 on 20 heads, the module's block, merge and second head pass, the held
experts' rows as the run's counters saw them routed) at the peak, over the device
time of the step's programs."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.step_mfu(reading)
