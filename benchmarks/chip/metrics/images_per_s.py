"""Frames classified per second over a closed run's whole window."""
from benchmarks.chip.readers import closed_rate


def read(rec):
    return closed_rate(rec)
