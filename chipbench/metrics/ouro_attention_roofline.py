"""Share of the roofline of the full-form flash kernels over the looped
stack's 32 applications (counts/ouro_step.py::attention_work over
``ouro_attention_ms``)."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.attention_roofline(reading)
