"""Per call: device time of the window-form flash kernels (``swa_fwd``, twice a
sliding layer under rematerialisation, ``swa_bwd_dq``, ``swa_bwd_dkv``), by
their names."""

from chipbench import trinity_trace


def read(reading):
    return trinity_trace.ms_per_call(reading, trinity_trace.WINDOW_ATTENTION)
