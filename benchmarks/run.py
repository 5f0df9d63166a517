"""Benchmark harness — one module per paper table / deliverable figure.

Prints ``name,us_per_call,derived`` CSV (assignment contract):
  * bench_conv_ladder    — paper Table 4 (heaviest conv layer × method)
  * bench_network_ladder — paper Table 3 (whole network × method, + FPS,
                           + fused super-layer vs unfused ladder rows)
  * bench_fc_fused       — paper §4 FC fusion (bias+act epilogue)
  * bench_serving        — deployment scenario throughput
  * roofline             — §Roofline terms from the dry-run artifacts
                           (rows appear when results/dryrun/ is populated)

``--json`` switches to the machine-readable path: the network ladder
runs, its per-network, per-method fused-vs-unfused numbers (us_per_call,
FPS, fused_speedup) are written to ``BENCH_network.json``, and batched
CNN-serving rows (``CNNServer`` throughput + p50/p95 latency at request
batches 1/8/16, ``--serving-batches``/``--serving-requests``;
``--no-serving`` skips) ride along under each network's ``serving`` key
so the perf trajectory records serving-scale numbers across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def _run_csv() -> int:
    """Print the CSV sweep; returns how many suites raised."""
    print("name,us_per_call,derived")
    from benchmarks import (  # noqa: E402
        bench_cnn_serving,
        bench_conv_ladder,
        bench_network_ladder,
        bench_fc_fused,
        bench_serving,
    )

    suites = [
        ("conv_ladder", bench_conv_ladder.run),
        ("network_ladder", bench_network_ladder.run),
        ("fc_fused", bench_fc_fused.run),
        ("serving", bench_serving.run),
        ("cnn_serving", bench_cnn_serving.run),
    ]
    failed = 0
    for name, fn in suites:
        try:
            for row in fn():
                print(f"{row['bench']},{row['us_per_call']:.1f},"
                      f"\"{row['derived']}\"", flush=True)
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            failed += 1
            print(f"{name},ERROR,\"{type(e).__name__}: {e}\"", flush=True)
            traceback.print_exc(file=sys.stderr)

    # roofline rows (dry-run artifacts under results/dryrun/)
    try:
        from pathlib import Path

        from benchmarks.roofline import load_all

        rows = load_all(Path("results/dryrun"), mesh="16x16")
        for r in rows:
            if "error" in r:
                continue
            print(f"roofline/{r['arch']}/{r['shape']},"
                  f"{max(r['compute_s'], r['memory_s'], r['collective_s'])*1e6:.1f},"
                  f"\"dominant={r['dominant']} useful={r['useful_ratio']:.2f}"
                  f" fits={r['fits_16gb']}\"", flush=True)
    except Exception as e:  # noqa: BLE001 — run the rest, then fail
        failed += 1
        print(f"roofline,ERROR,\"{type(e).__name__}: {e}\"", flush=True)
        traceback.print_exc(file=sys.stderr)
    return failed


def _run_json(nets, out_path: str, batch: int, iters: int,
              serving_batches, serving_requests: int) -> None:
    from benchmarks import bench_cnn_serving, bench_network_ladder

    data = bench_network_ladder.run_json(nets=nets, batch=batch, iters=iters)
    if serving_batches:
        bench_cnn_serving.add_serving_rows(
            data, nets, batches=serving_batches, requests=serving_requests)
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    for name, nd in data["networks"].items():
        for row in nd["rows"]:
            ratio = row.get("fused_speedup")
            print(f"  {name}/{row['method']}: "
                  f"unfused={row['unfused']['us_per_call']:.0f}us"
                  + (f" fused={row['fused']['us_per_call']:.0f}us"
                     f" fused_vs_unfused={ratio:.2f}x" if ratio else ""),
                  flush=True)
        for srow in nd.get("serving", []):
            mode = srow.get("mode", "normal")
            tag = f"batch{srow['batch']}" + (
                "" if mode == "normal" else f"-{mode}")
            line = (f"  {name}/cnn_server/{tag}: "
                    f"rps={srow['throughput_rps']:.1f} "
                    f"p50={srow['p50_us']:.0f}us p95={srow['p95_us']:.0f}us")
            if mode != "normal":
                line += (f" shed={srow['shed']} degraded={srow['degraded']}"
                         f" final={srow['final_method']}")
            print(line, flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_network.json instead of the CSV sweep")
    ap.add_argument("--nets", default="lenet5,cifar10",
                    help="comma-separated network names (json path)")
    ap.add_argument("--out", default="BENCH_network.json",
                    help="output path for --json")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--serving-batches", default="1,8,16",
                    help="comma-separated CNNServer max_batch sweep for the "
                         "json path (batched-serving rows)")
    ap.add_argument("--serving-requests", type=int, default=16,
                    help="requests per serving row (after bucket warm-up)")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the batched-serving rows on the json path")
    args = ap.parse_args(argv)
    from repro import compile_cache

    compile_cache.enable()
    if args.json:
        serving_batches = () if args.no_serving else tuple(
            int(b) for b in args.serving_batches.split(",") if b.strip())
        _run_json(tuple(n.strip() for n in args.nets.split(",") if n.strip()),
                  args.out, args.batch, args.iters,
                  serving_batches, args.serving_requests)
    elif _run_csv():
        sys.exit(1)


if __name__ == "__main__":
    main()
