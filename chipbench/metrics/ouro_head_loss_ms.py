"""Per call: device time of the head and the cross-entropy of the four exits
(the scope ``lm.head_loss``): one loop over 4 x 4,096 rows whose carry holds a
block of logits and the head's summed gradient."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, ouro_trace.HEAD_LOSS)
