"""The functions that compute a kernel's bytes and operations, and the
roofline arithmetic, against numbers worked by hand."""

import json
import os

import pytest

from chipbench import manifest, roofline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def parts():
    return manifest.load(REPO)


def _work(parts, cell_name, counts):
    cell = parts.cell(cell_name)
    return parts.module("counts", counts).work(parts.config(cell), cell["chips"])


def test_lloyd_counts_one_chip(parts):
    # 31 passes over 2^24 x 64 float32 (4 GiB each) + 2^24 int64 labels;
    # 4 * rows * 8 * 64 operations a pass
    w = _work(parts, "kmeans-fit-1chip", "lloyd")
    assert w["bytes"] == 31 * 4 * 2**30 + 2**24 * 8 == 133278203904
    assert w["flops"] == 31 * 4 * 2**24 * 8 * 64 == 1065151889408


def test_lloyd_counts_four_chips_are_four_times(parts):
    one, four = _work(parts, "kmeans-fit-1chip", "lloyd"), _work(parts, "kmeans-fit-4chip", "lloyd")
    assert four == {k: 4 * v for k, v in one.items()}


def test_cdist_counts(parts):
    # 40,000^2 float32 out (6.4 GB) + X read twice (2 x 2.88 MB); 2 m^2 18
    w = _work(parts, "cdist-susy-1chip", "cdist")
    assert w["bytes"] == 6_400_000_000 + 5_760_000
    assert w["flops"] == 57_600_000_000


def test_least_seconds_and_its_bound(parts):
    peak = parts.table("peaks")["TPU v5 lite"]
    assert (peak["flops_per_s"], peak["bytes_per_s"]) == (197e12, 819e9)
    km = roofline.least_seconds(_work(parts, "kmeans-fit-1chip", "lloyd"), peak, 1)
    assert km["bound"] == "bandwidth"
    assert km["seconds"] == pytest.approx(0.162733, rel=1e-4)  # 133.28 GB / 819 GB/s
    km4 = roofline.least_seconds(_work(parts, "kmeans-fit-4chip", "lloyd"), peak, 4)
    assert km4["seconds"] == pytest.approx(km["seconds"])
    cd = roofline.least_seconds(_work(parts, "cdist-susy-1chip", "cdist"), peak, 1)
    assert cd["bound"] == "bandwidth"
    assert cd["seconds"] == pytest.approx(7.8214e-3, rel=1e-4)
    assert roofline.least_seconds({"bytes": 1, "flops": 1e12}, peak, 1)["bound"] == "compute"
