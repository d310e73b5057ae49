"""Per call: device time of what stands round the flash kernels under their
scope (``scope_trace``'s piece ``attention_glue``): the ``jnp.pad`` copies of q,
k and v from heads of 64 to the kernels' 128 lanes and the slice back, the
transposes into the kernels' layout, the backward prologue's row sums."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, "attention_glue")
