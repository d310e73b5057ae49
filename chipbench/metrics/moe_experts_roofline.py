"""Share of the roofline of the expert layers' grouped matmuls
(counts/moe_experts.py over ``moe_experts_ms``)."""

from chipbench import lm_trace


def read(reading):
    return lm_trace.share_of_least(reading, lm_trace.EXPERTS, "moe_experts")
