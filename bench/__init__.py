"""Benchmark of the DVS serving stack on the chip (``python bench/run.py``)."""
