"""Per call: device time of everything under the short-convolution mixers
(the flax modules ``block<i>/conv``: both projections, the gates and the taps),
forward, recomputed and backward, by the program's scope map."""

from chipbench import lfm2_trace


def read(reading):
    return lfm2_trace.piece_ms(reading, lfm2_trace.CONV_MIXER)
