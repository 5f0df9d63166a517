"""Device time per forward of the operations XLA runs around the Pallas
kernels (layout transposes, pads, width-tap slices, the softmax), in a
closed run's traced window."""


def read(rec):
    t = rec["trace"]
    if t is None or rec["loop"] != "closed" or not rec["batches"]:
        return None
    return 1e3 * t["xla_s"] / len(rec["batches"])
