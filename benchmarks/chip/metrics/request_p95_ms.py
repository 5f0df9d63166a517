"""95th percentile, over every request due in an open run's window, of
the time from when the request was due to when its result existed.  A
request that never got an answer counts as answered when the generator
gave up on it."""
import numpy as np

from benchmarks.chip.readers import percentile_ms


def read(rec):
    if rec["loop"] != "open":
        return None
    done = np.where(np.isnan(rec["done"]), np.nanmax(rec["done"]),
                    rec["done"])
    return percentile_ms(done - rec["due"], 0.95)
