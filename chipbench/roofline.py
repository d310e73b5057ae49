"""The least time the chips could take for a piece of work, and a kernel's
share of it: the arithmetic behind every ``*_roofline`` metric."""

from __future__ import annotations


def least_seconds(work: dict, peak: dict, chips: int) -> dict:
    by_flops = work["flops"] / (peak["flops_per_s"] * chips)
    by_bytes = work["bytes"] / (peak["bytes_per_s"] * chips)
    return {
        "seconds": max(by_flops, by_bytes),
        "bound": "bandwidth" if by_bytes >= by_flops else "compute",
    }


def share(reading, counts_name: str):
    """Percent of the roofline: least time for the calls' work over the
    device time of the programs that did it (the configuration's
    ``roofline_modules``), averaged over the chips. None where the trace has
    no such program."""
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    spent = tr.module_time(reading.config["roofline_modules"]) / 1e9 / len(tr.devices)
    if spent <= 0:
        return None
    work = reading.parts.module("counts", counts_name).work(reading.config, reading.chips)
    least = least_seconds(work, reading.peak, reading.chips)
    reading.notes[counts_name + "_roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] * len(tr.calls) / spent
