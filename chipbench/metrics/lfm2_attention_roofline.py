"""Share of the roofline of the full-form flash kernels at the model's heads
of 64 (counts/lfm2_step.py::attention_work over ``lfm2_attention_ms``): the
kernels pad a head to 128 lanes, so half of every product is zeros and the share
is low by that."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.attention_roofline(reading)
