"""Batched CNN serving bench — ``CNNServer`` throughput and latency at
request-batch sizes 1/8/16 (the paper's §6.2 deployment scenario:
forward-only classification of incoming frames, batches of 16).

Each row drives a ``CNNServer`` over the engine's batch-bucketed jit
cache: a warm-up drain compiles the bucket outside the measured window,
then ``requests`` frames are submitted and served in dynamic batches of
``max_batch``, recording throughput (requests per second of server busy
time) and p50/p95 submit→done latency.  ``add_serving_rows`` grafts the
sweep into a ``BENCH_network.json`` dict (under each network's
``serving`` key) so the CI trend gate (``tools/bench_compare.py``)
tracks serving-scale numbers alongside the per-call ladder.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import CNNEngine
from repro.core.methods import Method
from repro.core.netdefs import NETWORKS
from repro.serving.cnn import CNNServer, ImageRequest
from repro.serving.degrade import DegradeController, default_ladder

DEFAULT_BATCHES: Tuple[int, ...] = (1, 8, 16)
DEFAULT_REQUESTS = 16
_METHOD = Method.ADVANCED_SIMD_8  # the ladder's fastest rung serves
OVERLOAD_BATCH = 8        # max_batch for the overload/degraded-mode row
OVERLOAD_REQUESTS = 64    # burst size (queue bound admits a quarter)


def _per_busy_s(stats: dict) -> float:
    """Requests served per second of server busy time (``0.0`` when no
    busy time accrued, never ``inf``)."""
    return stats["served"] / stats["busy_s"] if stats["busy_s"] > 0 else 0.0


def bench_network(name: str, batches: Iterable[int] = DEFAULT_BATCHES,
                  requests: int = DEFAULT_REQUESTS, fuse: bool = True):
    """Serving rows for one network: one dict per max_batch setting."""
    net = NETWORKS[name]()
    eng = CNNEngine(net, method=_METHOD, fuse_pool=fuse)
    params = eng.init(jax.random.PRNGKey(0))
    n_imgs = min(requests, 32)
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (n_imgs, *net.input_shape), jnp.float32))
    rows = []
    rid = 0
    for b in batches:
        srv = CNNServer(eng, params, max_batch=b, max_delay_s=0.0)
        # warm-up outside the clock: one full batch compiles bucket b,
        # and when the measured drain ends on a ragged tail
        # (requests % b), that tail's bucket is compiled too
        warm_sizes = [b] + ([requests % b] if requests % b else [])
        for size in warm_sizes:
            for _ in range(size):
                srv.submit(ImageRequest(rid=rid, image=imgs[rid % n_imgs]))
                rid += 1
            srv.run_until_drained()
        srv.reset_stats()
        for _ in range(requests):
            srv.submit(ImageRequest(rid=rid, image=imgs[rid % n_imgs]))
            rid += 1
        srv.run_until_drained()
        s = srv.stats()
        rows.append({
            "batch": b,
            "requests": requests,
            "throughput_rps": _per_busy_s(s),
            "p50_us": s["p50_latency_us"],
            "p95_us": s["p95_latency_us"],
            "mean_batch": s["mean_batch"],
        })
    return rows


def bench_overload(name: str, *, max_batch: int = OVERLOAD_BATCH,
                   requests: int = OVERLOAD_REQUESTS) -> dict:
    """One degraded-mode row: a scripted overload burst against a
    queue-bounded server wearing the degradation ladder.

    The burst submits ``requests`` frames into a queue capped at
    ``4 * max_batch`` — the overflow is rejected at admission (typed
    sheds, counted) — and the degradation controller (pressure
    threshold ``max_batch``, single-observation trigger: this row
    measures the degraded steady state, not the hysteresis, which the
    tier-1 tests cover) walks the server down at least one
    ``CNNEngine.switch_verified``-blessed rung while draining.  The row
    records the shed/degraded counters next to the usual latency and
    throughput numbers; the downgrade recompile lands inside the
    measured window deliberately — that is the cost overload actually
    pays."""
    net = NETWORKS[name]()
    eng = CNNEngine(net, method=_METHOD, fuse_pool=True)
    params = eng.init(jax.random.PRNGKey(0))
    n_imgs = 32
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (n_imgs, *net.input_shape), jnp.float32))
    # two rungs only: one honest downgrade, not a walk to the floor
    ladder = default_ladder(_METHOD, fuse=True)[:2]
    ctl = DegradeController(ladder, queue_high=max_batch, degrade_after=1,
                            recover_after=10 ** 9, cooldown=0)
    srv = CNNServer(eng, params, max_batch=max_batch, max_delay_s=0.0,
                    max_queue=4 * max_batch, degrade=ctl)
    rid = 0
    for _ in range(max_batch):  # warm the primary bucket off the clock
        srv.submit(ImageRequest(rid=rid, image=imgs[rid % n_imgs]))
        rid += 1
    srv.run_until_drained()
    srv.reset_stats()
    for _ in range(requests):
        srv.submit(ImageRequest(rid=rid, image=imgs[rid % n_imgs]))
        rid += 1
    srv.run_until_drained()
    s = srv.stats()
    return {
        "mode": "degraded",
        "batch": max_batch,
        "requests": requests,
        "served": s["served"],
        "rejected": s["rejected"],
        "shed": s["shed"],
        "degraded": s["degraded"],
        "final_method": eng.method.value,
        "throughput_rps": _per_busy_s(s),
        "p50_us": s.get("p50_latency_us", 0.0),
        "p95_us": s.get("p95_latency_us", 0.0),
        "mean_batch": s["mean_batch"],
    }


def add_serving_rows(data: dict, nets: Iterable[str],
                     batches: Iterable[int] = DEFAULT_BATCHES,
                     requests: int = DEFAULT_REQUESTS,
                     overload: bool = True) -> dict:
    """Graft serving rows into a ``run_json`` bench dict (in place).

    Rows land under ``networks[name]["serving"]`` and the sweep config
    under ``serving_config`` — ``bench_compare`` resets the serving
    baseline (rows report as ``new``) when the config changes, mirroring
    the top-level batch/iters/backend handling.  ``overload`` appends
    the degraded-mode row (``bench_overload``) per network, flattened by
    the trend gate as variant ``batchN-degraded``."""
    batches = tuple(batches)
    data["serving_config"] = {"batches": list(batches),
                              "requests": requests,
                              "method": _METHOD.value, "fused": True}
    if overload:
        data["serving_config"]["overload"] = {
            "batch": OVERLOAD_BATCH, "requests": OVERLOAD_REQUESTS}
    for name in nets:
        rows = bench_network(name, batches=batches, requests=requests)
        if overload:
            rows.append(bench_overload(name))
        data.setdefault("networks", {}).setdefault(name, {})["serving"] = rows
    return data


def run(nets=("lenet5", "cifar10"), batches=DEFAULT_BATCHES,
        requests=DEFAULT_REQUESTS):
    """CSV-harness rows (``name,us_per_call,derived``): p50 latency as
    the headline number, throughput/p95 derived."""
    out = []
    for name in nets:
        for row in bench_network(name, batches=batches, requests=requests):
            out.append({
                "bench": f"cnn_serving/{name}/batch{row['batch']}",
                "us_per_call": row["p50_us"],
                "derived": (f"rps={row['throughput_rps']:.1f} "
                            f"p95_us={row['p95_us']:.0f} "
                            f"mean_batch={row['mean_batch']:.1f}"),
            })
        orow = bench_overload(name)
        out.append({
            "bench": f"cnn_serving/{name}/overload",
            "us_per_call": orow["p50_us"],
            "derived": (f"rps={orow['throughput_rps']:.1f} "
                        f"served={orow['served']} shed={orow['shed']} "
                        f"degraded={orow['degraded']} "
                        f"final={orow['final_method']}"),
        })
    return out
