"""Quickstart — the paper's core scenario end-to-end.

1. "Train" LeNet-5 server-side (random init stands in for Caffe training;
   the deploy pipeline is identical), convert + save the deployable model.
2. Load it device-side and run the forward path over a batch of 16 frames
   (paper §6.2) under every execution method of the ladder.
3. Print the per-method runtime and speedup over the sequential reference —
   a miniature of the paper's Table 3.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.core.deploy import save_model, load_model
from repro.core.engine import CNNEngine
from repro.core.methods import Method, LADDER
from repro.core.netdefs import NETWORKS


def main():
    # -- train side -----------------------------------------------------------
    net = NETWORKS["lenet5"]()
    engine = CNNEngine(net)
    params = engine.init(jax.random.PRNGKey(0))
    path = tempfile.mkdtemp(prefix="cnndroid_model_")
    save_model(path, net, params, {"trained_with": "examples/quickstart.py"})
    print(f"[deploy] saved {net.name} -> {path}")

    # -- device side ------------------------------------------------------------
    net2, params2, extra = load_model(path)
    print(f"[deploy] loaded {net2.name} (extra={extra})")
    x = jax.random.normal(jax.random.PRNGKey(1), (16, *net2.input_shape),
                          jnp.float32)  # batch of 16 frames, paper §6.2

    print(f"\n{'method':20s} {'ms/batch':>10s} {'speedup':>9s}  (vs §4.1 sequential)")
    base = None
    for method in LADDER:
        eng = CNNEngine(net2, method=method)
        fn = eng.jit_forward()
        jax.block_until_ready(fn(params2, x))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(params2, x)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 5 * 1e3
        base = base or ms
        print(f"{method.value:20s} {ms:10.2f} {base/ms:8.2f}x")
    probs = out
    print(f"\npredictions: {jnp.argmax(probs, -1).tolist()}")
    print(f"(timings are {jax.default_backend()} XLA path; the paper's "
          "Table 3 ladder ordering is the reproduction target)")


if __name__ == "__main__":
    main()
