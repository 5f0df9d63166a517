"""Finds the knee of an open-loop mix: the highest offered rate the
server sustains with no growing backlog.

    python3 -m benchmarks.chip.sweep --config alexnet \\
        --traffic frames-poisson-480 --seed 3 --seconds 5 \\
        --rates 400 500 600 700

A configuration and a mix are named by their files, so a mix can be
swept before any cell of ``BENCHMARK.json`` runs it.  One process, one
set-up (the engine, weights, frame pool and the mix's loop warmed up);
then the mix at each rate in turn for ``--seconds``.  Prints one JSON
line per rate: requests offered and answered, the answered rate, the
median and 95th percentile of the time from due to answer, and the
mean of that time over the window's last fifth against its first
fifth, which grows with the rate once the queue does.  Used once, on
the chip, to fix an open mix's rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from benchmarks.chip.run import ROOT


def sweep_rates(config_name: str, traffic_name: str, seed: int,
                seconds: float, rates) -> list:
    import jax

    from benchmarks.chip import loadgen, reference, run, spec
    from repro.core.engine import CNNEngine
    from repro.core.netdefs import NETWORKS

    config = spec.config(config_name)
    traffic = spec.traffic(traffic_name)
    gen = spec.loop(traffic["loop"])
    engine = CNNEngine(NETWORKS[config["name"]](), **run.ENGINE_KW)
    params = jax.jit(engine.init)(reference.weights_key(seed))
    pool = loadgen.frame_pool(config, traffic, seed)
    server = gen.setup(engine, params, pool, traffic)
    rows = []
    for rate in rates:
        server.done.clear()
        server.reset_stats()
        t = dict(traffic, rate_per_s=rate)
        rec = gen.drive(server, pool, t, seconds, seed)
        lat = rec["done"] - rec["due"]
        ok = ~np.isnan(lat)
        fifth = len(lat) // 5
        row = {"rate_per_s": rate, "offered": len(lat),
               "answered": int(ok.sum()),
               "answered_per_s": float(ok.sum() / np.nanmax(rec["done"])),
               "p50_ms": float(1e3 * np.nanmedian(lat)),
               "p95_ms": float(1e3 * np.nanquantile(lat, 0.95)),
               "first_fifth_mean_ms": float(1e3 * np.nanmean(lat[:fifth])),
               "last_fifth_mean_ms": float(1e3 * np.nanmean(lat[-fifth:])),
               "mean_batch": rec["server_stats"]["mean_batch"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("the sweep needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    sweep_rates(args.config, args.traffic, args.seed, args.seconds,
                args.rates)
    return 0


if __name__ == "__main__":
    sys.exit(main())
