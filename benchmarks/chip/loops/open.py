"""The open loop: requests of one frame arrive on a schedule drawn from
the seed and are submitted to a ``CNNServer(max_batch=...)``, with its
default ``max_delay_s``, whether or not it keeps up.  The schedule
holds ``round(rate_per_s * seconds)`` arrivals placed uniformly at
random in the window, which is a Poisson process given its count:
every seed gets the same amount of work, in another order.  Request
``r`` carries frame ``r`` of the pool, cyclically, and asks for its
``top_k`` classes.

Mix keys: ``rate_per_s``, ``max_batch``, ``top_k``, ``pool_frames``.
"""
from __future__ import annotations

import time
from types import SimpleNamespace
from typing import Callable, Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.chip import check

#: how long the loop waits after its window for the last requests
#: before it calls them lost
DRAIN_LIMIT_S = 60.0


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of every request,
    sorted."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    rng = np.random.default_rng([seed, 1])
    return np.sort(rng.uniform(0.0, seconds, n))


def setup(engine, params, pool, traffic: dict):
    """The server, after serving one batch of every size from 1 to
    ``max_batch``: that compiles each bucket's program and the
    host-side padding of each size.  Its results and counters are left
    empty."""
    from repro.serving.cnn import CNNServer, ImageRequest

    server = CNNServer(engine, params, max_batch=traffic["max_batch"])
    rid = -1
    for n in range(1, traffic["max_batch"] + 1):
        for j in range(n):
            server.submit(ImageRequest(rid=rid, image=pool[j % len(pool)],
                                       top_k=traffic["top_k"]))
            rid -= 1
        server.step(force=True)
    server.run_until_drained()
    server.done.clear()
    server.reset_stats()
    return server


def drive(server, pool, traffic: dict, seconds: float, seed: int,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> dict:
    """Submit every request at its due time and step the server until
    all are answered.  Returns per request its due, submit and done
    times (seconds from the window's start; done is NaN for a request
    that never got an answer) and the results."""
    from repro.serving.cnn import ImageRequest

    due = arrivals(traffic, seconds, seed)
    n = len(due)
    submit_t = np.full(n, np.nan)
    done_t = np.full(n, np.nan)
    results: Dict[int, object] = {}
    n_pool = len(pool)
    top_k = traffic["top_k"]
    max_delay = server.max_delay_s
    t0 = clock()
    nxt = 0
    while True:
        now = clock() - t0
        while nxt < n and due[nxt] <= now:
            with TraceAnnotation("bench.submit"):
                shed = server.submit(ImageRequest(
                    rid=nxt, image=pool[nxt % n_pool], top_k=top_k))
            submit_t[nxt] = clock() - t0
            if shed is not None:
                results[nxt] = server.pop_result(nxt)
            nxt += 1
        with TraceAnnotation("bench.step"):
            out = server.step()
        t_done = clock() - t0
        for res in out:
            # take each result out of the server, as a client would
            results[res.rid] = server.pop_result(res.rid)
            done_t[res.rid] = t_done
        if nxt >= n and server.pending() == 0:
            break
        if nxt >= n and t_done > seconds + DRAIN_LIMIT_S:
            break
        if not out:
            wait = max_delay / 4
            if nxt < n:
                wait = min(wait, due[nxt] - t_done)
            if wait > 0:
                with TraceAnnotation("bench.idle"):
                    sleep(wait)
    return {"loop": "open", "due": due, "submit": submit_t, "done": done_t,
            "results": results, "server_stats": server.stats()}


def readings(rec: dict, ref: np.ndarray) -> dict:
    return check.open_readings(rec, ref)


def counts(rec: dict, readings: dict) -> tuple:
    """Requests due in the window, and those that got no answer."""
    return len(rec["due"]), int(readings["missing"])


def notes(rec: dict) -> List[str]:
    late = rec["submit"] - rec["due"]
    return [f"generator lateness: p95 {1e3 * np.nanquantile(late, 0.95):.3f}"
            f" ms, max {1e3 * np.nanmax(late):.3f} ms over {len(late)} "
            f"requests"]


def control_record(probs: np.ndarray, traffic: dict) -> dict:
    """The record of a window that served every pool frame's top-k from
    ``probs``, as the server does."""
    k = traffic["top_k"]
    n = len(probs)
    results = {}
    for r in range(n):
        top = np.argsort(-probs[r], kind="stable")[:k]
        results[r] = SimpleNamespace(top_indices=list(top),
                                     top_probs=list(probs[r][top]))
    return {"loop": "open", "due": np.zeros(n), "done": np.zeros(n),
            "results": results}
