"""The whole forward's share of the chip's peak: frames per second times
the configuration's FLOPs per frame over the peak FLOP/s, in percent
(closed runs, traced)."""
from benchmarks.chip import work
from benchmarks.chip.readers import closed_rate


def read(rec):
    rate = closed_rate(rec)
    if rec["trace"] is None or rate is None:
        return None
    flops = work.flops_per_frame(rec["config"])
    return 100.0 * rate * flops / rec["peaks"]["bf16_flops_per_s"]
