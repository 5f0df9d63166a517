"""ResNet-50's configuration against the program: the network the
harness runs, its weights, the grouping its work is counted by, and a
residual network at test size against ``reference.py`` on the CPU
(the XLA path, and the Pallas path in interpret mode)."""
import dataclasses

import numpy as np
import pytest

from benchmarks.chip import reference, run, spec
from repro.core.engine import CNNEngine
from repro.core.netdefs import NETWORKS, LayerSpec, NetworkDef

#: plan steps whose layers the conv family counts (``work.FAMILY``)
CONV_STEPS = ("conv", "fused", "chain", "residual", "pool", "lrn", "add")


def tiny_resnet() -> dict:
    return spec._json("testdata", "tiny_resnet", spec.HERE)


def netdef(config: dict) -> NetworkDef:
    """The program's ``NetworkDef`` of a configuration that states every
    layer's ``inputs``."""
    layers = []
    for row in config["layers"]:
        kw = {"inputs": tuple(row["inputs"])}
        if row["kind"] in ("conv", "fc"):
            kw.update(out_channels=row["out"], relu=row["relu"],
                      gain=row.get("gain", 1.0))
        if row["kind"] in ("conv", "pool"):
            kw.update(kernel=tuple(row["kernel"]),
                      stride=tuple(row["stride"]),
                      padding=tuple(row["pad"]))
        if row["kind"] == "pool":
            kw.update(pool_kind=row["pool"], relu=row["relu"])
        if row["kind"] == "add":
            kw["relu"] = row["relu"]
        layers.append(LayerSpec(row["kind"], row["name"], **kw))
    return NetworkDef(config["name"], tuple(config["input"]),
                      config["num_classes"], tuple(layers))


def plan_groups(net) -> dict:
    """Each family's groups as the compiled Pallas plan runs them."""
    plan = CNNEngine(net, **run.ENGINE_KW).plan(True)
    return {"conv": [list(s.names) for s in plan.steps
                     if s.kind in CONV_STEPS],
            "fc": [list(s.names) for s in plan.steps if s.kind == "fc"]}


def test_resnet50_is_the_configured_network():
    run.check_network(spec.config("resnet50"), NETWORKS["resnet50"]())


def test_a_miswired_resnet50_is_refused():
    net = NETWORKS["resnet50"]()
    layers = tuple(dataclasses.replace(s, inputs=("s1b1_a",))
                   if s.name == "s1b1_proj" else s for s in net.layers)
    with pytest.raises(ValueError, match="differs"):
        run.check_network(spec.config("resnet50"),
                          dataclasses.replace(net, layers=layers))


def test_the_test_size_network_is_its_configuration():
    run.check_network(tiny_resnet(), netdef(tiny_resnet()))


@pytest.mark.parametrize("name", ["resnet50", "tiny_resnet"])
def test_the_configured_groups_are_the_plans(name):
    config = spec.config(name) if name == "resnet50" else tiny_resnet()
    net = NETWORKS[name]() if name == "resnet50" else netdef(config)
    want = {f: k["groups"] for f, k in config["kernels"].items()}
    assert plan_groups(net) == want
    assert "conv2d_residual" in config["kernels"]["conv"]["match"]


def test_resnet50_weights_are_the_engines_draws():
    seed = 2**31 + 53
    want = CNNEngine(NETWORKS["resnet50"]()).init(
        reference.weights_key(seed))
    got = reference.make_params(spec.config("resnet50"), seed)
    assert sorted(got) == sorted(want)
    for layer in got:
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[layer][k], want[layer][k])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_tiny_resnet_matches_the_reference(use_pallas):
    config = tiny_resnet()
    seed = 2**32 + 19
    frames = np.random.default_rng(4).standard_normal(
        (2, *config["input"])).astype(np.float32)
    engine = CNNEngine(netdef(config), use_pallas=use_pallas)
    params = engine.init(reference.weights_key(seed))
    out = np.asarray(engine.forward_batched(params, frames))
    ref = reference.probabilities(config, seed, frames, block=2)
    assert out.shape == ref.shape == (2, config["num_classes"])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=0)
    kinds = {s.label: s.kind for s in engine.plan().steps}
    assert kinds["b1_c+b1_add" if use_pallas else "b1_add"] == (
        "residual" if use_pallas else "add")
