"""Device time per plan step of a closed cell, on one chip.

    python3 -m benchmarks.chip.step_table --workload alexnet.video16 \\
        --seed 2147483659 --batches 100

from the root of a checkout.  Sets the cell up as ``run.py`` does, runs
``--batches`` batches of its closed loop under the profiler (the spans
of ``loops/closed.py`` inside ``bench.window``), and joins the trace to
the program's op→step map (``steps.py``).  Prints one line per plan
step on standard error and, as the last line of standard output, a JSON
object: ``step_s``, ``mapped_share`` (percent of the window's device
time under a step), and the conv and fc rooflines of the kernels alone
(``conv_roofline``, ``fc_roofline``, as the benchmark's readers compute
them) and of every operation under their plan steps
(``plan.conv_roofline``, ``plan.fc_roofline``).  Exits 2 without a TPU
and 1 for a program without ``CNNEngine.op_steps``.

With ``--testdata`` it also writes the trace and ``{"batches",
"op_steps", "step_s"}`` to ``testdata/<cell>.steps.xplane.pb`` and
``.steps.json``; ``test_chipbench_steps.py`` reads those of
``cifar10.video16`` at two batches.  Re-record them when the compiled
program's instruction names change.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

from benchmarks.chip import run

# the trace puts device events about a millisecond earlier than the
# host's: a lead-in keeps the first batch's operations inside the window
LEAD_IN_S = 0.01


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="cifar10.video16")
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--testdata", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.ROOT), str(run.ROOT / "src")]
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from benchmarks.chip import loadgen, readers, reference, spec, steps
    from benchmarks.chip import trace as tr
    from repro import compile_cache
    from repro.core.engine import CNNEngine
    from repro.core.netdefs import NETWORKS

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = spec.workload(spec.load_benchmark(run.ROOT), args.workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    engine = CNNEngine(NETWORKS[config["name"]](), **run.ENGINE_KW)
    params = jax.block_until_ready(
        jax.jit(engine.init)(reference.weights_key(args.seed)))
    pool = loadgen.frame_pool(config, traffic, args.seed)
    op_steps = steps.op_steps(engine, params, pool, traffic)
    if op_steps is None:
        print(f"{args.workload}: the program gives no op→step map",
              file=sys.stderr)
        return 1
    b = traffic["batch"]
    for _ in range(2):
        np.asarray(engine.forward_batched(params, jax.device_put(pool[:b])))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-steps-")
    batches = []
    try:
        tr.start(trace_dir)
        with TraceAnnotation(tr.WINDOW_SPAN):
            time.sleep(LEAD_IN_S)
            for i in range(args.batches):
                first = (i * b) % len(pool)
                with TraceAnnotation("bench.stage"):
                    x = jax.device_put(pool[first:first + b])
                with TraceAnnotation("bench.forward_batched"):
                    y = engine.forward_batched(params, x)
                with TraceAnnotation("bench.fetch"):
                    np.asarray(y)
                batches.append((first, 0.0, 0.0))
        jax.profiler.stop_trace()
        path = tr.find_xplane(trace_dir)
        device, host = tr.load(path)
        if args.testdata:
            out = spec.HERE / "testdata" / (
                args.workload.replace(".", "_") + ".steps")
            shutil.copyfile(path, f"{out}.xplane.pb")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    match = {f: k["match"] for f, k in config["kernels"].items()}
    summary = tr.reduce(device, host, match, 1)
    step_s = steps.reduce(device, host, op_steps, 1)
    if args.testdata:
        (out.parent / f"{out.name}.json").write_text(json.dumps(
            {"batches": args.batches, "op_steps": op_steps,
             "step_s": step_s}, indent=1) + "\n")
    rec = {"loop": "closed", "batch": b, "batches": batches,
           "config": config, "peaks": tr.peaks_for(
               jax.devices()[0].device_kind, spec.HERE),
           "trace": dict(summary, step_s=step_s)}
    for line in steps.notes(rec):
        print(line, file=sys.stderr)
    mapped = sum(r["kernels_s"] + r["other_s"]
                 for r in step_s["steps"].values())
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "batches": args.batches, "step_s": step_s,
        "mapped_share": 100.0 * mapped / (mapped + step_s["unmapped_s"]),
        **{f"{f}_roofline": readers.roofline_percent(rec, f)
           for f in ("conv", "fc")},
        **{f"plan.{f}_roofline": steps.plan_roofline_percent(rec, f)
           for f in ("conv", "fc")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
