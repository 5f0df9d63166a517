"""95th percentile, over every batch of a closed run's window, of the
time from staging the batch to having its probabilities on the host."""
from benchmarks.chip.readers import percentile_ms


def read(rec):
    if rec["loop"] != "closed" or not rec["batches"]:
        return None
    return percentile_ms([done - start for _, start, done in rec["batches"]],
                         0.95)
