"""Per call: device time of everything under the six latent mixers (the flax
modules ``block<i>/attn``, the module's among them: projections, norms, rotary,
joins and the flash kernels), forward, recomputed and backward, by the program's
scope map."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "latent")
