"""Key blocks the window kernels' grids visit over the blocks that hold a live
pair (the program's counters ``attn.window.blocks_visited`` over
``attn.window.blocks_live``, from its static grids): 1.0 is a grid that covers
the band and no more."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.blocks_visited_over_live()
