"""Share of the roofline of the window-form flash kernels: the band's
operations and the bytes a banded kernel must move once
(counts/swa_attention.py) over ``swa_attention_ms``."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.share_of_least(reading, trinity_trace.WINDOW_ATTENTION, "swa_attention")
