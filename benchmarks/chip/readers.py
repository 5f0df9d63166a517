"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the run's record: the generator's output (``loop``
and its timings), the cell's ``config`` and ``traffic``, the device's
``peaks``, ``setup_s``, ``window_s`` and, in a traced run, ``trace``
(``trace.summarize``).  A reader returns ``None`` where the record holds
nothing for it to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from benchmarks.chip import work


def closed_frames(rec: dict) -> Optional[int]:
    """Frames classified in a closed run's window."""
    if rec["loop"] != "closed" or not rec["batches"]:
        return None
    return rec["batch"] * len(rec["batches"])


def closed_rate(rec: dict) -> Optional[float]:
    """Frames per second of a closed run: every frame classified over the
    time from the window's start to the last batch's end."""
    n = closed_frames(rec)
    if n is None:
        return None
    return n / rec["batches"][-1][2]


def idle_percent(rec: dict) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in percent."""
    t = rec["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_percent(rec: dict, family: str) -> Optional[float]:
    """The family's ideal time over the device time of its kernels, in
    percent, for a closed run's traced window: every forward in the
    window did the family's work once."""
    t = rec["trace"]
    n = closed_frames(rec)
    if t is None or n is None or t["family_s"].get(family, 0.0) <= 0:
        return None
    groups = work.group_work(rec["config"], rec["batch"])[family]
    ideal = work.ideal_s(groups, rec["peaks"]) * len(rec["batches"])
    return 100.0 * ideal / t["family_s"][family]


def percentile_ms(values, q: float) -> float:
    return float(1e3 * np.quantile(np.asarray(values, np.float64), q))
