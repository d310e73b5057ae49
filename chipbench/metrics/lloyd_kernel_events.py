"""``lloyd_update`` kernel events per call and chip: the iterations the
program really ran."""

from chipbench import program_spans


def read(reading):
    return program_spans.kernel_events_per_call(reading, program_spans.LLOYD_KERNEL)
