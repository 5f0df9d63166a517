"""The traffic generator: a mix is a data file, ``traffic/<mix>.json``,
whose ``loop`` names the loop that drives the system under test with
its parameters, ``loops/<loop>.py`` (found by name, ``spec.loop``).  A
new mix of an existing loop is data alone; a new kind of arrival
process or client is a new loop file.

Every frame a loop sends comes from one pool drawn from the seed.
Every call a loop makes into the system is wrapped in a host span
(``jax.profiler.TraceAnnotation``) named ``bench.<what>``, so that a
trace can say what the host was doing while the device idled.

A loop module defines:

* ``setup(engine, params, pool, traffic)``: warm up every shape the
  loop will use; returns the state ``drive`` takes;
* ``drive(state, pool, traffic, seconds, seed)``: the window; returns
  the run's record, with ``loop`` set to the loop's name;
* ``readings(rec, ref)``: the numbers ``check.decide`` compares, from
  the record and the reference's probabilities of every pool frame;
* ``counts(rec, readings)``: the requests attempted and failed;
* ``notes(rec)``: lines for standard error (how late the generator ran,
  the slowest call);
* ``control_record(probs, traffic)``: the record of a window that
  answered every pool frame with ``probs``, for the control.
"""
from __future__ import annotations

import numpy as np


def frame_pool(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """``pool_frames`` distinct standard-normal frames from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((traffic["pool_frames"], *config["input"]),
                               dtype=np.float32)
