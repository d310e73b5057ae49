"""The benchmark of heat_tpu on the chip: `python3 chipbench/run.py`."""
