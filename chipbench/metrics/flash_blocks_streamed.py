"""Key blocks the full causal flash kernels' grids copy over the blocks that
hold a live pair (the program's counters ``attn.full.blocks_streamed`` over
``attn.full.blocks_live``, from its static grids): 1.0 is a grid that copies
nothing above the diagonal. The note ``flash_blocks`` holds the two counts and,
where the program says it, the grid's steps a head (``visited``): what is
left of them past ``live`` are steps that copy and compute nothing. A program
without the counters (a parent commit) reads None."""

from chipbench.lm_trace import counter


def read(reading):
    streamed, live = counter("attn.full.blocks_streamed"), counter("attn.full.blocks_live")
    if streamed is None or not live:
        return None
    note = reading.notes["flash_blocks"] = {"streamed": streamed, "live": live}
    try:
        from heat_tpu.parallel.pallas_attention import causal_grid

        t = reading.config["sequence_length"]
        note["visited_a_head"], _, note["live_a_head"] = causal_grid(t, t)
    except (ImportError, KeyError):
        pass
    return streamed / live
