"""How often a step runs a block body it holds: the flash forward kernels a call
in the device trace over the forward kernel's call sites in the compiled step:
4 for the looped stack, 1 for one written out or unrolled."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.loop_passes(reading)
