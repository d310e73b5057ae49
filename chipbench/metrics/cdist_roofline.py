"""Share of the roofline of the distance matrix's programs (counts/cdist.py)."""

from chipbench import roofline


def read(reading):
    return roofline.share(reading, "cdist")
