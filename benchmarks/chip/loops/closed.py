"""The closed loop: one client sends back-to-back batches of ``batch``
frames through ``CNNEngine.forward_batched``.  Each batch is staged from
host memory to the device and its probabilities are fetched to the
host before the next is sent.  Batch ``i`` holds frames ``i*batch ..``
of the frame pool, cyclically.

Mix keys: ``batch``, ``pool_frames``.
"""
from __future__ import annotations

import time
from typing import Callable, List

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.chip import check


def setup(engine, params, pool, traffic: dict):
    """Compile and run the loop's one shape."""
    b = traffic["batch"]
    for _ in range(2):
        np.asarray(engine.forward_batched(params, jax.device_put(pool[:b])))
    return engine, params


def drive(state, pool, traffic: dict, seconds: float, seed: int,
          clock: Callable[[], float] = time.perf_counter) -> dict:
    """Back-to-back batches for ``seconds``.  Returns the window's
    batches as ``(first_frame, t_start, t_done)`` (seconds from the
    window's start), their probabilities, and the slowest batch split
    into staging, dispatch and fetch."""
    engine, params = state
    b = traffic["batch"]
    n_pool = len(pool)
    if n_pool % b:
        raise ValueError(f"pool_frames {n_pool} is not a multiple of the "
                         f"batch {b}")
    batches: List[tuple] = []
    outputs: List[np.ndarray] = []
    slowest = (0.0, 0.0, 0.0, 0.0, 0.0)
    t0 = clock()
    i = 0
    while True:
        t_start = clock() - t0
        if t_start >= seconds:
            break
        first = (i * b) % n_pool
        with TraceAnnotation("bench.stage"):
            x = jax.device_put(pool[first:first + b])
        t_staged = clock() - t0
        with TraceAnnotation("bench.forward_batched"):
            y = engine.forward_batched(params, x)
        t_called = clock() - t0
        with TraceAnnotation("bench.fetch"):
            probs = np.asarray(y)
        t_done = clock() - t0
        batches.append((first, t_start, t_done))
        outputs.append(probs)
        if t_done - t_start > slowest[0]:
            slowest = (t_done - t_start, t_start, t_staged - t_start,
                       t_called - t_staged, t_done - t_called)
        i += 1
    return {"loop": "closed", "batch": b, "batches": batches,
            "outputs": outputs, "slowest": slowest}


def readings(rec: dict, ref: np.ndarray) -> dict:
    return check.closed_readings(rec, ref)


def counts(rec: dict, readings: dict) -> tuple:
    """Frames attempted and failed: a batch either answers or raises."""
    return rec["batch"] * len(rec["batches"]), 0


def notes(rec: dict) -> List[str]:
    dur, at, stage, dispatch, fetch = (1e3 * t for t in rec["slowest"])
    return [f"slowest batch: {dur:.1f} ms at {at / 1e3:.3f} s (stage "
            f"{stage:.1f}, dispatch {dispatch:.1f}, fetch {fetch:.1f} ms)"]


def control_record(probs: np.ndarray, traffic: dict) -> dict:
    """The record of a window that answered every pool frame with
    ``probs``, batch by batch."""
    b = traffic["batch"]
    n = len(probs)
    return {"loop": "closed", "batch": b,
            "batches": [(i, 0.0, 0.0) for i in range(0, n, b)],
            "outputs": [probs[i:i + b] for i in range(0, n, b)]}
