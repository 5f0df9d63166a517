"""Share of a closed run's traced window with no operation on the
device, in percent."""
from benchmarks.chip.readers import idle_percent


def read(rec):
    return idle_percent(rec) if rec["loop"] == "closed" else None
