"""The busiest held expert's rows over an even share of its layer's assignments
(64 experts sharing them evenly reads 1.0), the worst of the expert layers, the
module's among them, mean over the window's calls, from the counts each step
returns (``aux["expert_counts"]``)."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.held_load(reading)
