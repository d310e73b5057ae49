"""The share of a step's assignments that land on the held experts, over an
even share (held / all experts), mean over the steps the process made (the
program's counters ``moe.held_share`` over ``moe.steps``): 1.0 is an even load."""

from chipbench import trinity_trace


def read(reading):
    total, steps = trinity_trace.counter("moe.held_share"), trinity_trace.counter("moe.steps")
    if total is None or not steps:
        return None
    return total / steps * reading.config["num_experts"] / reading.config["num_experts_held"]
