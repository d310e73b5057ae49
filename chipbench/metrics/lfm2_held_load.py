"""The share of a step's assignments that land on the held experts, over an
even share (held / all experts), mean over the steps the process made (the
program's counters ``moe.held_share`` over ``moe.steps``): 1.0 is an even load."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.held_load(reading)
