"""Per call: device time of the clip and AdamW's update (the scopes
``train.optimizer`` and ``train.clip``)."""

from chipbench import ouro_trace


def read(reading):
    return ouro_trace.piece_ms(reading, ouro_trace.OPTIMIZER)
