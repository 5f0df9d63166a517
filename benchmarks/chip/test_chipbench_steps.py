"""``steps.py`` and its plan-level rooflines: on hand-made events and
records, and on a small trace recorded on one v5e (two batches of the
cifar10 closed loop, ``testdata/``, made by ``step_table.py
--testdata``)."""
import json

import pytest

from benchmarks.chip import spec, steps, work
from benchmarks.chip import trace as tr

TESTDATA = spec.HERE / "testdata"
V5E = tr.peaks_for("TPU v5 lite", spec.HERE)


def _op(name, kernel):
    target = ', custom_call_target="tpu_custom_call"' if kernel else ""
    return f"%{name} = f32[16,10]{{1,0}} custom-call(){target}"


OP_STEPS = {"x.1": "", "copy.1": "", "slice.2": "conv1+pool1",
            "conv2d.3": "conv1+pool1", "matmul_fused.2": "fc1",
            "fusion.1": "prob"}


def test_device_time_per_step_unmapped_and_window_clip():
    ms = 1_000_000  # ns
    device = {"/device:TPU:0": [
        (_op("slice.2", False), 0.5 * ms, 1.5 * ms),  # half before the window
        (_op("conv2d.3", True), 1 * ms, 3 * ms),
        (_op("copy.1", False), 3 * ms, 4 * ms),       # under no step
        (_op("mystery.7", False), 4 * ms, 4.5 * ms),  # not in the map
        (_op("matmul_fused.2", True), 5 * ms, 6 * ms),
        (_op("fusion.1", False), 6 * ms, 6.25 * ms),
        (_op("conv2d.3", True), 11 * ms, 12 * ms),    # after the window
    ], "/device:TPU:1": [(_op("conv2d.3", True), 1 * ms, 9 * ms)]}
    host = [("bench.window", 1 * ms, 10 * ms), ("bench.stage", 1 * ms,
                                                2 * ms)]
    got = steps.reduce(device, host, OP_STEPS, 1)
    assert list(got["steps"]) == ["conv1+pool1", "fc1", "prob"]
    assert got["steps"]["conv1+pool1"] == pytest.approx(
        {"kernels_s": 0.002, "other_s": 0.0005})
    assert got["steps"]["fc1"] == pytest.approx(
        {"kernels_s": 0.001, "other_s": 0.0})
    assert got["steps"]["prob"] == pytest.approx(
        {"kernels_s": 0.0, "other_s": 0.00025})
    assert got["unmapped_s"] == pytest.approx(0.0015)
    assert got["unmapped_ops"] == pytest.approx({"copy": 0.001,
                                                 "mystery": 0.0005})
    # two chips: each step's seconds are averaged over them
    both = steps.reduce(device, host, OP_STEPS, 2)
    assert both["steps"]["conv1+pool1"]["kernels_s"] == pytest.approx(
        (0.002 + 0.008) / 2)


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        steps.reduce({"/device:TPU:0": []}, [], OP_STEPS, 1)


def _record(step_s, batches=2):
    return {"loop": "closed", "batch": 16,
            "batches": [(16 * i, 0.0, 0.0) for i in range(batches)],
            "config": spec.config("cifar10"), "peaks": V5E,
            "trace": {"step_s": step_s}}


def _read(metric, rec):
    return spec.metric_reader(metric).read(rec)


def _plan(family, rec):
    return steps.plan_roofline_percent(rec, family)


def test_plan_rooflines_count_every_operation_under_the_family():
    step_s = {"steps": {
        "conv1+pool1": {"kernels_s": 0.001, "other_s": 0.002},
        "conv2+pool2": {"kernels_s": 0.003, "other_s": 0.0},
        "conv3+pool3": {"kernels_s": 0.001, "other_s": 0.001},
        "fc1": {"kernels_s": 0.0005, "other_s": 0.0001},
        "fc2": {"kernels_s": 0.0002, "other_s": 0.0002},
        "prob": {"kernels_s": 0.0, "other_s": 0.004}},   # in no family
        "unmapped_s": 0.5, "unmapped_ops": {"copy": 0.5}}
    rec = _record(step_s)
    groups = work.group_work(rec["config"], 16)
    conv = work.ideal_s(groups["conv"], V5E) * 2 / 0.008
    fc = work.ideal_s(groups["fc"], V5E) * 2 / 0.001
    assert _plan("conv", rec) == pytest.approx(100 * conv)
    assert _plan("fc", rec) == pytest.approx(100 * fc)


def test_plan_rooflines_read_nothing_where_nothing_maps():
    no_fc = {"steps": {"conv1+pool1": {"kernels_s": 0.001, "other_s": 0.0},
                       "prob": {"kernels_s": 0.0, "other_s": 0.001}},
             "unmapped_s": 0.0, "unmapped_ops": {}}
    assert _plan("fc", _record(no_fc)) is None
    assert _plan("conv", _record(no_fc)) is not None
    # a program without the map, an untraced run, an open run
    for rec in (_record(None), dict(_record(no_fc), trace=None),
                dict(_record(no_fc), loop="open")):
        assert _plan("conv", rec) is None
        assert steps.notes(rec) == []
    assert steps.notes(_record(no_fc)) == [
        "step conv1+pool1: kernels 0.5000 ms, other ops 0.0000 ms per "
        "forward",
        "step prob: kernels 0.0000 ms, other ops 0.5000 ms per forward",
        "under no step: 0.0000 ms per forward, 0.000% of device time ()"]


def test_an_engine_without_the_map_gives_none():
    assert steps.op_steps(object(), {}, None, {"batch": 16}) is None


def test_recorded_trace_maps_to_plan_steps_as_on_the_chip():
    want = json.loads((TESTDATA / "cifar10_video16.steps.json").read_text())
    device, host = tr.load(TESTDATA / "cifar10_video16.steps.xplane.pb")
    got = steps.reduce(device, host, want["op_steps"], 1)
    assert json.loads(json.dumps(got)) == want["step_s"]  # the same sums
    assert list(got["steps"]) == ["conv1+pool1", "conv2+pool2",
                                  "conv3+pool3", "flatten", "fc1", "fc2",
                                  "prob"]
    # the same operations as trace.reduce's, split by step
    config = spec.config("cifar10")
    summary = tr.reduce(device, host, {f: k["match"] for f, k in
                                       config["kernels"].items()}, 1)
    mapped = sum(r["kernels_s"] + r["other_s"]
                 for r in got["steps"].values())
    assert mapped + got["unmapped_s"] == pytest.approx(
        sum(summary["family_s"].values()) + summary["xla_s"])
    assert mapped >= 0.95 * (mapped + got["unmapped_s"])
    kernels = {label: r["kernels_s"] for label, r in got["steps"].items()}
    assert sum(kernels[f"conv{i}+pool{i}"] for i in (1, 2, 3)) == \
        pytest.approx(summary["family_s"]["conv"])
    assert kernels["fc1"] + kernels["fc2"] == pytest.approx(
        summary["family_s"]["fc"])
    # with its layout ops counted, the conv layer reads below its kernels
    rec = dict(_record(got, want["batches"]), trace=dict(summary,
                                                         step_s=got))
    assert 0 < _plan("conv", rec) < _read("conv_roofline", rec)
    assert 0 < _plan("fc", rec) <= _read("fc_roofline", rec)


def test_the_step_table_needs_a_tpu(capsys):
    from benchmarks.chip import step_table

    assert step_table.main(["--workload", "cifar10.video16"]) == 2
    assert capsys.readouterr().out == ""
