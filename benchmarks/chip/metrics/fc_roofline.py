"""The fc layers' ideal time over the device time of the kernels that
implement them, in percent (closed runs, traced)."""
from benchmarks.chip.readers import roofline_percent


def read(rec):
    return roofline_percent(rec, "fc")
