"""The harness on the CPU: it finds every piece by name, refuses to run
without a TPU, and reports ``correct`` false when the timed path gives
a wrong answer.  The look for a chip is skipped by calling
``run.run_cell`` directly; the Pallas kernels run in interpret mode."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import run, spec
from benchmarks.chip import trace as tr
from benchmarks.chip.test_chipbench_work import tiny_graph
from repro.core.engine import CNNEngine

ROOT = run.ROOT

#: LeNet-5 (CNNdroid Table 2), small enough for interpret mode; a
#: configuration put down as a new file in these tests
LENET5 = {
    "name": "lenet5", "source": "CNNdroid Table 2", "input": [1, 28, 28],
    "num_classes": 10,
    "dtype": {"weights": "float32", "activations": "float32",
              "contraction": "float32", "matmul_precision": "highest"},
    "layers": [
        {"kind": "conv", "name": "conv1", "out": 20, "kernel": [5, 5],
         "stride": [1, 1], "pad": [0, 0], "relu": False},
        {"kind": "pool", "name": "pool1", "pool": "max", "kernel": [2, 2],
         "stride": [2, 2], "relu": False},
        {"kind": "conv", "name": "conv2", "out": 50, "kernel": [5, 5],
         "stride": [1, 1], "pad": [0, 0], "relu": False},
        {"kind": "pool", "name": "pool2", "pool": "max", "kernel": [2, 2],
         "stride": [2, 2], "relu": False},
        {"kind": "flatten", "name": "flatten"},
        {"kind": "fc", "name": "fc1", "out": 500, "relu": True},
        {"kind": "fc", "name": "fc2", "out": 10, "relu": False},
        {"kind": "softmax", "name": "prob"}],
    "kernels": {"conv": {"groups": [["conv1", "pool1"], ["conv2", "pool2"]],
                         "match": ["conv2d"]},
                "fc": {"groups": [["fc1"], ["fc2"]],
                       "match": ["matmul_fused"]}},
}

NEW_METRIC = '''"""Frames answered in the window (a metric added as a file)."""
import numpy as np


def read(rec):
    if "batches" in rec:
        return float(rec["batch"] * len(rec["batches"]))
    return float(np.sum(~np.isnan(rec["done"])))
'''


NEW_LOOP = '''"""The closed loop, put down again under a new name."""
import jax
import numpy as np

from benchmarks.chip import check


def setup(engine, params, pool, traffic):
    return engine, params


def drive(state, pool, traffic, seconds, seed):
    engine, params = state
    b = traffic["batch"]
    outputs = [np.asarray(engine.forward_batched(
        params, jax.device_put(pool[i:i + b])))
        for i in range(0, len(pool), b)]
    return {"loop": "closed-again", "batch": b, "outputs": outputs,
            "batches": [(i, 0.0, 0.0) for i in range(0, len(pool), b)]}


def readings(rec, ref):
    return check.closed_readings(rec, ref)


def counts(rec, readings):
    return rec["batch"] * len(rec["batches"]), 0


def notes(rec):
    return [f"{len(rec['batches'])} batches"]


def control_record(probs, traffic):
    return {}
'''


@pytest.fixture
def added(tmp_path, monkeypatch):
    """A copy of the benchmark with a configuration, two traffic mixes,
    their limits and a metric put down as new files, and a
    ``BENCHMARK.json`` that names them.  The CPU has no entry in the
    peaks table, so the v5e's stands in."""
    base = tmp_path / "chip"
    shutil.copytree(spec.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "configs" / "lenet5.json").write_text(json.dumps(LENET5))
    (base / "traffic" / "tiny-closed.json").write_text(json.dumps(
        {"loop": "closed", "batch": 4, "pool_frames": 8}))
    (base / "traffic" / "tiny-open.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 40, "max_batch": 4, "top_k": 3,
         "pool_frames": 8}))
    # a new kind of loop is a new file that the mix names
    (base / "loops" / "closed-again.py").write_text(NEW_LOOP)
    (base / "traffic" / "tiny-again.json").write_text(json.dumps(
        {"loop": "closed-again", "batch": 2, "pool_frames": 8}))
    limits = {"logp_err": 1e-3}
    (base / "limits" / "lenet5.tiny-closed.json").write_text(
        json.dumps({"limits": limits}))
    (base / "limits" / "lenet5.tiny-open.json").write_text(
        json.dumps({"limits": dict(limits, missing=0)}))
    (base / "limits" / "lenet5.tiny-again.json").write_text(
        json.dumps({"limits": limits}))
    (base / "metrics" / "frames_answered.py").write_text(NEW_METRIC)
    bench = spec.load_benchmark(ROOT)
    cells = ["lenet5.tiny-closed", "lenet5.tiny-open", "lenet5.tiny-again"]
    bench["workloads"] += [
        {"name": c, "config": "lenet5", "traffic": c.split(".")[1],
         "chips": 1, "why": "test"} for c in cells]
    for m in bench["end_to_end"]:
        if m["name"] in ("images_per_s", "batch_p95_ms"):
            m["workloads"].append(cells[0])
    # metrics put down as entries: one with a reader of its own, one
    # whose reader is already there and no cell of the benchmark uses
    bench["end_to_end"] += [
        {"name": "frames_answered", "unit": "frames", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": cells},
        {"name": "request_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": cells[1:2]}]
    v5e = tr.peaks_for("TPU v5 lite", spec.HERE)
    monkeypatch.setattr(tr, "peaks_for", lambda kind, base: v5e)
    yield bench, base
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def _run(bench, base, cell):
    return run.run_cell(bench, cell, 2**31 + 3, 0.3, False,
                        time.perf_counter(), base=base)


def test_new_files_are_found_by_name(added):
    bench, base = added
    closed = _run(bench, base, "lenet5.tiny-closed")
    assert closed["correct"], closed["checks"]
    assert set(closed["metrics"]) == {"images_per_s", "batch_p95_ms",
                                      "setup_s", "frames_answered"}
    assert closed["metrics"]["frames_answered"]["value"] == \
        closed["attempted"] > 0
    opened = _run(bench, base, "lenet5.tiny-open")
    assert opened["correct"], opened["checks"]
    assert set(opened["metrics"]) == {"request_p95_ms", "setup_s",
                                      "frames_answered"}
    assert opened["failed"] == 0
    assert list(opened)[-1] == "checks"
    again = _run(bench, base, "lenet5.tiny-again")
    assert again["correct"], again["checks"]
    assert set(again["metrics"]) == {"setup_s", "frames_answered"}
    assert again["attempted"] == 8


@pytest.mark.parametrize("cell", ["lenet5.tiny-closed", "lenet5.tiny-open"])
def test_an_altered_answer_is_not_correct(added, monkeypatch, cell):
    bench, base = added
    honest = CNNEngine.forward_batched

    def altered(self, params, x, fuse=None):
        out = honest(self, params, x, fuse)
        return out.at[0].set(out[0][::-1])  # one answer, classes reversed

    monkeypatch.setattr(CNNEngine, "forward_batched", altered)
    out = _run(bench, base, cell)
    assert not out["correct"]
    assert out["checks"]["logp_err"]["value"] > \
        out["checks"]["logp_err"]["limit"]


def test_every_named_piece_exists():
    bench = spec.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        config = spec.config(cell["config"])
        assert spec.loop(spec.traffic(cell["traffic"])["loop"])
        assert spec.limits(cell["name"])
        run.check_network(config, __import__(
            "repro.core.netdefs", fromlist=["NETWORKS"]).NETWORKS[
                config["name"]]())
        for per_layer in (False, True):
            assert spec.cell_metrics(bench, cell["name"], per_layer)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def _stand_in(config: dict) -> SimpleNamespace:
    """A network object as a program that read every key of the
    configuration would build it."""
    layers = []
    for layer in config["layers"]:
        spec_ = SimpleNamespace(kind=layer["kind"], name=layer["name"],
                                relu=layer.get("relu", False))
        if "out" in layer:
            spec_.out_channels = layer["out"]
        for key, field in (("kernel", "kernel"), ("stride", "stride"),
                           ("pad", "padding"), ("inputs", "inputs")):
            if key in layer:
                setattr(spec_, field, tuple(layer[key]))
        for key, field in (("pool", "pool_kind"), ("groups", "groups"),
                           ("gain", "gain")):
            if key in layer:
                setattr(spec_, field, layer[key])
        layers.append(spec_)
    return SimpleNamespace(name=config["name"],
                           input_shape=tuple(config["input"]),
                           num_classes=config["num_classes"], layers=layers)


def test_check_network_passes_a_program_that_reads_the_graph():
    run.check_network(tiny_graph(), _stand_in(tiny_graph()))


@pytest.mark.parametrize("fault", [
    # the add reads the block's input instead of the projection
    lambda ls: setattr(ls["b1_add"], "inputs", ("b1_conv3", "pool0")),
    # the shortcut reads the wrong layer
    lambda ls: setattr(ls["b1_proj"], "inputs", ("stem",)),
    # the conv ignores groups: no such field, or a dense conv
    lambda ls: delattr(ls["b1_conv2"], "groups"),
    lambda ls: setattr(ls["b1_conv2"], "groups", 1),
    # the pool ignores pad
    lambda ls: setattr(ls["pool0"], "padding", (0, 0)),
    # the draw ignores the gain
    lambda ls: delattr(ls["b1_conv3"], "gain"),
    # the add leaves out its ReLU
    lambda ls: setattr(ls["b1_add"], "relu", False),
], ids=["add-input", "shortcut-input", "groups-missing", "groups-1",
        "pool-pad", "gain-missing", "add-relu"])
def test_check_network_refuses_a_program_that_misreads_the_graph(fault):
    net = _stand_in(tiny_graph())
    fault({spec_.name: spec_ for spec_ in net.layers})
    with pytest.raises(ValueError, match="differs"):
        run.check_network(tiny_graph(), net)


def test_benchmark_names_and_units():
    bench = spec.load_benchmark(ROOT)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    things = bench["configs"] + bench["workloads"] + bench["end_to_end"] \
        + bench["per_layer"]
    for t in things:
        assert name.match(t["name"]), t["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert sorted(bench) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "alexnet.video16", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs 1 TPU" in proc.stderr
