"""Per call: device time of the full-form flash kernels (``flash_fwd``,
``flash_bwd_*``) of the six latent mixers: 20 heads of 256."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.attention_ms(reading)
