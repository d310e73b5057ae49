"""Per call: device time of the full-form flash kernels (``flash_fwd``,
``flash_bwd_*``) of the looped stack: 32 forward and 32 fused backward a step at
16 heads of 128."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.attention_ms(reading)
