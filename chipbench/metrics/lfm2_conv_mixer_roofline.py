"""Share of the roofline of the short-convolution mixers: their two
projections' products forward and backward and the bytes that enter and leave a
mixer once each way (counts/lfm2_step.py::conv_mixer_work) over
``lfm2_conv_mixer_ms``, which times exactly the fusions that do that work (XLA
fuses the gates and the taps into them) and their second run under
rematerialisation, which is no work: a mixer at the peak in all three passes
reads 75%."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.conv_mixer_roofline(reading)
