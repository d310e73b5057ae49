"""Rows that the work round the held experts gathers and sums back into their
tokens (blocks run x a block's rows, or a window's length where it is moved
whole; every window of every expert layer), over the assignments that landed on
the held experts, over the steps the process made (the program's counters
``moe.held_rows_moved`` over ``moe.held_assignments``): 1.0 is movement that
touches live rows only, 2 a window of two even shares moved whole under an even
routing. A program that keeps no such counter: None."""

from chipbench import lm_trace


def read(reading):
    moved, held = lm_trace.counter("moe.held_rows_moved"), lm_trace.counter("moe.held_assignments")
    return moved / held if moved is not None and held else None
