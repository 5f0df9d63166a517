"""The control's readings, which set the upper end of a cell's limits.

    python3 -m benchmarks.chip.control --config alexnet \
        --traffic video16 --seeds 11 12 13

The control is the reference computed one precision below the
configuration's (``reference.py``, ``"high"``: ``Precision.HIGH``,
three bfloat16 passes on a TPU, for float32 at ``highest``), put in the
program's place on the mix's frames and read by the same comparison as
a run: the mix's loop (``control_record``) turns its probabilities
into the record a window would hold.  A configuration and a mix are
named by their files, so a cell can be read before ``BENCHMARK.json``
holds it.  Prints one JSON line per seed.  The program's readings are
those its runs print (``run.py``); the lower reading of a number is the
largest of those, the upper reading the smallest the control gives
(``PERF.md`` records both).
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmarks.chip.run import ROOT


def control_readings(config: dict, traffic: dict, seed: int) -> dict:
    """The comparison's readings with the control in the program's
    place, over every frame of the mix's pool."""
    from benchmarks.chip import loadgen, reference, spec

    gen = spec.loop(traffic["loop"])
    pool = loadgen.frame_pool(config, traffic, seed)
    ref = reference.probabilities(config, seed, pool)
    ctl = reference.probabilities(config, seed, pool, precision="high")
    return gen.readings(gen.control_record(ctl, traffic), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from benchmarks.chip import spec
    from repro import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("the control needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    config = spec.config(args.config)
    traffic = spec.traffic(args.traffic)
    for seed in args.seeds:
        print(json.dumps({"config": args.config, "traffic": args.traffic,
                          "seed": seed,
                          "control": control_readings(config, traffic,
                                                      seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
