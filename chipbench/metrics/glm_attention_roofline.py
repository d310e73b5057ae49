"""Share of the roofline of the full-form flash kernels at 20 heads of 256 for
queries, keys and values (counts/glm_step.py::attention_work: the causal pairs
counted once, over ``glm_attention_ms``)."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.attention_roofline(reading)
