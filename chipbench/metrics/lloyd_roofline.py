"""Share of the roofline of the K-Means fit's programs (counts/lloyd.py)."""

from chipbench import roofline


def read(reading):
    return roofline.share(reading, "lloyd")
