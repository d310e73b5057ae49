"""Per call: device time of the fusions that hold an instruction of the scope
``mla.assemble`` (rotary on the two rope parts, the broadcast of the one rotary
key over the heads and the joins forward; the splits and the sum over the heads
backward), their own and those they are fused into, all passes: an upper bound
on what a kernel that took the rotary key as an operand of its own would save."""

from chipbench import glm_trace


def read(reading):
    return glm_trace.tag_ms(reading, "latent_assemble")
