"""Runs one cell of the chip benchmark and prints its result line.

    python3 -m benchmarks.chip.run --workload alexnet.video16 \\
        --seed 7 --seconds 20 --trace 0

from the root of a checkout.  One process, in this order: check that JAX
sees a TPU with as many chips as the cell asks for (else exit 2 with no
result); make the weights on the device from the seed through
``CNNEngine.init``; warm up the cell's own shapes, with JAX's persistent
compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` or else the
checkout's ``.jax_cache``; measure for ``--seconds``; check that nothing
compiled inside the window; free the program's state and compare what
the window produced with ``reference.py``; print the result as the last
line of standard output and the compared numbers as the last lines of
standard error.

With ``--trace 1`` the window runs under the profiler and the result
carries the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown; with ``--trace 0`` its end-to-end metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

#: the root of the checkout this file lies in
ROOT = Path(__file__).resolve().parents[2]

#: the engine every cell runs: the Pallas path with its default method
#: and fusion, as deployed
ENGINE_KW = {"use_pallas": True}


def check_network(config: dict, net) -> None:
    """Refuse a program whose network differs from the configuration:
    the yardstick's work must be the program's work."""
    want = []
    for layer in config["layers"]:
        kind = layer["kind"]
        row = {"kind": kind, "name": layer["name"]}
        if kind in ("conv", "fc"):
            row["out_channels"] = layer["out"]
            row["relu"] = layer["relu"]
        if kind == "conv":
            row.update(kernel=tuple(layer["kernel"]),
                       stride=tuple(layer["stride"]),
                       padding=tuple(layer["pad"]))
        if kind == "pool":
            row.update(kernel=tuple(layer["kernel"]),
                       stride=tuple(layer["stride"]),
                       pool_kind=layer["pool"], relu=layer["relu"])
        if kind == "lrn":
            row.update(lrn_n=layer["size"], lrn_alpha=layer["alpha"],
                       lrn_beta=layer["beta"], lrn_k=layer["k"])
        want.append(row)
    have = [{k: getattr(spec, k) for k in row}
            for spec, row in zip(net.layers, want)]
    if (list(net.input_shape) != list(config["input"])
            or net.num_classes != config["num_classes"]
            or len(net.layers) != len(want) or have != want):
        raise ValueError(f"the program's {net.name} differs from "
                         f"configs/{config['name']}.json")


class CompileCounter:
    """Counts the programs JAX lowers while it is installed: each new
    shape, whether compiled or read from the persistent cache, is
    lowered once."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def _gc_timer(times: list):
    """A ``gc.callbacks`` entry that appends each collection's seconds."""
    start = []

    def on(phase, info):
        if phase == "start":
            start[:] = [time.perf_counter()]
        elif start:
            times.append(time.perf_counter() - start[0])
    return on


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_setup0: float, base: Path = None) -> dict:
    """Set up, measure, check and reduce one run of a cell; returns the
    result line as a dict (``checks`` last)."""
    import jax

    from benchmarks.chip import check, loadgen, reference, spec
    from benchmarks.chip import trace as tr
    from repro.core.engine import CNNEngine
    from repro.core.netdefs import NETWORKS

    base = base or spec.HERE
    cell = spec.workload(bench, cell_name)
    config = spec.config(cell["config"], base)
    traffic = spec.traffic(cell["traffic"], base)
    gen = spec.loop(traffic["loop"], base)
    limits = spec.limits(cell_name, base)
    devices = jax.devices()[:cell["chips"]]
    peaks = tr.peaks_for(devices[0].device_kind, base)

    net = NETWORKS[config["name"]]()
    check_network(config, net)
    engine = CNNEngine(net, **ENGINE_KW)
    params = jax.block_until_ready(
        jax.jit(engine.init)(reference.weights_key(seed)))
    pool = loadgen.frame_pool(config, traffic, seed)
    state = gen.setup(engine, params, pool, traffic)
    counter = CompileCounter()
    buckets_before = engine.bucket_stats()["compiles"]
    setup_s = time.perf_counter() - t_setup0

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            tr.start(trace_dir)
        gc_times = []
        gc_timer = _gc_timer(gc_times)
        gc.callbacks.append(gc_timer)
        t_window = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            rec = gen.drive(state, pool, traffic, seconds, seed)
        window_s = time.perf_counter() - t_window
        gc.callbacks.remove(gc_timer)
        print(f"gc in the window: {len(gc_times)} collections, "
              f"{1e3 * sum(gc_times):.1f} ms, longest "
              f"{1e3 * max(gc_times, default=0):.1f} ms", file=sys.stderr)
        if trace:
            jax.profiler.stop_trace()
        compiled = counter.count
        counter.close()
        bucket_compiles = engine.bucket_stats()["compiles"] - buckets_before
        if compiled or bucket_compiles:
            print(f"compiled inside the window: {compiled} programs lowered,"
                  f" {bucket_compiles} bucket compiles", file=sys.stderr)
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
        trace_summary = (tr.summarize(tr.find_xplane(trace_dir), config,
                                      len(devices)) if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    del params, state, engine
    ref = reference.probabilities(config, seed, pool)
    readings = gen.readings(rec, ref)
    attempted, failed = gen.counts(rec, readings)
    for line in gen.notes(rec):
        print(line, file=sys.stderr)
    correct, rows = check.decide(readings, limits)

    rec.update(cell=cell, config=config, traffic=traffic, peaks=peaks,
               setup_s=setup_s, window_s=window_s, trace=trace_summary)
    metrics = {}
    for m in spec.cell_metrics(bench, cell_name, per_layer=trace):
        value = spec.metric_reader(m["name"], base).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace_summary is not None:
        device.update(busy_s=trace_summary["busy_s"],
                      window_s=trace_summary["window_s"])
        out["breakdown"] = trace_summary["breakdown"]
    out["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                     for r in rows}
    return out


def main(argv=None) -> int:
    t_setup0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import spec

    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro import compile_cache

    # every program is kept, the small ones too, so that a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"compile cache: {compile_cache.enable()}", file=sys.stderr)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_setup0)
    for name, row in out["checks"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
