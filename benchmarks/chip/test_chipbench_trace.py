"""``trace.py`` on a small trace recorded on one v5e (two batches of the
cifar10 closed loop, ``testdata/``) and on hand-made events."""
import json

import pytest

from benchmarks.chip import spec
from benchmarks.chip import trace as tr

TESTDATA = spec.HERE / "testdata"


def test_recorded_trace_reduces_as_on_the_chip():
    config = spec.config("cifar10")
    got = tr.summarize(TESTDATA / "cifar10_video16.xplane.pb", config, 1)
    want = json.loads(
        (TESTDATA / "cifar10_video16.summary.json").read_text())["summary"]
    assert json.loads(json.dumps(got)) == want  # the same reduction
    assert 0 < got["busy_s"] < got["window_s"]
    # the three fused conv kernels and both fc kernels are matched
    kernels = [name for name, _ in got["breakdown"]["device_ops"]
               if name.startswith(("conv2d", "matmul_fused"))]
    assert {k.split(".")[0] for k in kernels} == {"conv2d", "matmul_fused"}
    assert set(got["family_s"]) == {"conv", "fc"}
    # every idle gap is attributed to a benchmark span
    assert {g for g, _ in got["breakdown"]["idle_gaps"]} <= {
        "bench.stage", "bench.forward_batched", "bench.fetch", "no span"}


def _op(name, kernel):
    target = ', custom_call_target="tpu_custom_call"' if kernel else ""
    return f"%{name} = f32[16,10]{{1,0}} custom-call(){target}"


def test_busy_union_idle_share_and_kernel_matching():
    ms = 1_000_000  # ns
    device = {"/device:TPU:0": [
        (_op("conv2d.3", True), 1 * ms, 3 * ms),
        (_op("pad.1", False), 2 * ms, 4 * ms),       # overlaps the kernel
        (_op("matmul_fused.2", True), 6 * ms, 7 * ms),
        (_op("mystery.1", True), 8 * ms, 9 * ms),    # a kernel of no family
        (_op("conv2d.9", True), 11 * ms, 12 * ms),   # after the window
    ]}
    host = [("bench.window", 0, 10 * ms), ("bench.stage", 0, 1 * ms),
            ("bench.fetch", 4 * ms, 6 * ms), ("bench.stage", 9 * ms,
                                              10 * ms)]
    got = tr.reduce(device, host, {"conv": ["conv2d"],
                                   "fc": ["matmul_fused"]}, 1)
    assert got["window_s"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.005)  # [1,4] + [6,7] + [8,9]
    assert got["family_s"] == pytest.approx(
        {"conv": 0.002, "fc": 0.001, "other_kernels": 0.001})
    assert got["xla_s"] == pytest.approx(0.002)
    gaps = dict(got["breakdown"]["idle_gaps"])
    # [0,1] in stage, [4,6] in fetch, [7,8] outside any span, [9,10] stage
    assert gaps == pytest.approx({"bench.stage": 0.002, "bench.fetch": 0.002,
                                  "no span": 0.001})


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce({"/device:TPU:0": []}, [], {}, 1)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        tr.peaks_for("TPU v9 imaginary", spec.HERE)
