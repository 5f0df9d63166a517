"""Smoke run of the Pallas CNN path on one TPU chip.

Drives the main inference path through its user entry points —
``CNNEngine(use_pallas=True)`` with the default method,
``CNNEngine.forward_batched`` and ``CNNServer`` — at the paper's
published widths (lenet5, cifar10, alexnet; batch 16, paper §6.2), with
random weights made from ``--seed``.  Each network's output is compared
on the same chip with a ``Method.SEQ_REF`` engine run at fp32 matmul
precision.  Exits non-zero, before printing any result, when JAX finds no
TPU or any phase fails.  The last line of standard output is one JSON
object naming the device.

    python chip_smoke.py [--seed N]

Millisecond figures printed on the way are smoke timings of one run, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

BATCH = 16
#: max |Δprob| against the fp32 reference — the interpret-mode bound of
#: tests/test_fusion.py
TOL = 1e-4
#: the fused dispatches the default plan must run, per network
EXPECTED_GROUPS = {
    "lenet5": ["conv1+pool1", "conv2+pool2"],
    "cifar10": ["conv1+pool1", "conv2+pool2", "conv3+pool3"],
    "alexnet": ["conv1+pool1+norm1", "conv2+pool2+norm2",
                "conv3+conv4+conv5+pool5"],
}
#: top-k probabilities of one frame, served vs direct forward: two
#: compiled programs of the same fp32 math
TOPK_TOL = 1e-6
#: ragged arrival groups for the server phase (40 requests)
ARRIVALS = (5, 11, 3, 16, 1, 4)
TOP_K = 5


def _check_plan(eng, name, params, x):
    """The engine must run the fused Pallas groups, every fc through
    ``matmul_fused``, and lower to TPU kernels — no silent per-layer or
    XLA path."""
    groups = [g["group"] for g in eng.fusion_report()]
    if groups != EXPECTED_GROUPS[name]:
        raise AssertionError(f"{name}: fused groups {groups}, expected "
                             f"{EXPECTED_GROUPS[name]}")
    kinds = [s.kind for s in eng.plan().steps]
    if "conv" in kinds or "pool" in kinds:
        raise AssertionError(f"{name}: per-layer conv/pool steps in {kinds}")
    n_fc = kinds.count("fc")
    hlo = jax.jit(eng.forward).lower(params, x).as_text()
    n_kernels = hlo.count("tpu_custom_call")
    if n_kernels < len(groups) + n_fc:
        raise AssertionError(f"{name}: {n_kernels} TPU kernels in the "
                             f"program, expected >= {len(groups) + n_fc}")
    return groups, n_kernels


def _run_network(name, seed):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import CNNEngine
    from repro.core.methods import Method
    from repro.core.netdefs import NETWORKS

    net = NETWORKS[name]()
    eng = CNNEngine(net, use_pallas=True)
    params = eng.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (BATCH, *net.input_shape), jnp.float32)
    groups, n_kernels = _check_plan(eng, name, params, x)
    t0 = time.perf_counter()
    out = jax.block_until_ready(eng.forward_batched(params, x))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(eng.forward_batched(params, x))
    warm_ms = (time.perf_counter() - t0) * 1e3
    ref_eng = CNNEngine(net, method=Method.SEQ_REF)
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(ref_eng.jit_forward()(params, x))
    out, ref = np.asarray(out), np.asarray(ref)
    if out.shape != (BATCH, net.num_classes) or not np.all(np.isfinite(out)):
        raise AssertionError(f"{name}: output shape {out.shape} or "
                             "non-finite values")
    err = float(np.max(np.abs(out - ref)))
    print(f"{name}: groups={groups} tpu_kernels={n_kernels} "
          f"first_call_s={compile_s:.1f} max_abs_dprob={err:.3e} "
          f"(smoke timing, not a benchmark result: warm forward "
          f"{warm_ms:.2f} ms)", flush=True)
    if not err < TOL:
        raise AssertionError(f"{name}: max |dprob| {err:.3e} >= {TOL}")
    return eng, params


def _run_server(eng, params, seed):
    import numpy as np

    from repro.serving.cnn import CNNServer, ImageRequest, ImageResult

    rng = np.random.default_rng(seed)
    n = sum(ARRIVALS)
    frames = rng.standard_normal((n, *eng.net.input_shape)).astype(np.float32)
    server = CNNServer(eng, params, max_batch=BATCH)
    rid = 0
    for group in ARRIVALS:
        for _ in range(group):
            shed = server.submit(ImageRequest(rid=rid, image=frames[rid],
                                              top_k=TOP_K))
            if shed is not None:
                raise AssertionError(f"request {rid} shed: {shed}")
            rid += 1
        time.sleep(0.005)  # let the oldest request age past max_delay_s
        server.step()
    done = server.run_until_drained()
    bad = {r: res for r, res in done.items()
           if not isinstance(res, ImageResult)}
    if len(done) != n or bad:
        raise AssertionError(f"{len(done)}/{n} requests done; not served: "
                             f"{bad}")
    direct = np.concatenate([
        np.asarray(eng.forward_batched(params, frames[i:i + BATCH]))
        for i in range(0, n, BATCH)])
    for r, res in done.items():
        p = direct[r]
        want = np.argsort(-p, kind="stable")[:TOP_K]
        got = np.asarray(res.top_indices)
        # an index may differ only where two probabilities tie
        if not np.allclose(p[got], res.top_probs, atol=TOPK_TOL) or \
                not np.allclose(p[got], p[want], atol=TOPK_TOL):
            raise AssertionError(f"request {r}: top-{TOP_K} {got.tolist()} "
                                 f"vs direct {want.tolist()}")
    compiles = eng.bucket_stats()["compiles"]
    stats = server.stats()
    print(f"server: {json.dumps(stats, default=str)} "
          f"(latencies and rates are smoke timings, not benchmark results)",
          flush=True)
    if compiles > 5:
        raise AssertionError(f"{compiles} bucket compiles, expected <= 5")
    print(f"server: {n} requests served as ImageResult, top-{TOP_K} matches "
          f"the direct forward, bucket compiles={compiles}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compile_cache

    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={compile_cache.enable()}", flush=True)
    engines = {}
    for name in ("lenet5", "cifar10", "alexnet"):
        engines[name] = _run_network(name, args.seed)
    _run_server(*engines["alexnet"], args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        sys.exit(1)
