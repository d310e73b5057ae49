"""The busiest expert's assignments over the mean expert's, mean over the steps
the process made (the program's counters ``moe.load_max_over_mean`` over
``moe.steps``; 1.0 is an even load)."""

from chipbench import lm_trace


def read(reading):
    total, steps = lm_trace.counter("moe.load_max_over_mean"), lm_trace.counter("moe.steps")
    return total / steps if total is not None and steps else None
