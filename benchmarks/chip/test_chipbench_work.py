"""``work.py`` against hand-worked layers of the committed
configurations and of a small branching graph."""
import json

import pytest

from benchmarks.chip import spec, work


def _rows(name):
    return {r["name"]: r for r in work.layer_shapes(spec.config(name))}


#: alexnet conv2 by hand: 27x27 outputs x 256 channels, each over 5x5
#: taps of 96 channels
CONV2_MACS = 447_897_600


def test_alexnet_conv2_macs_by_hand():
    side, channels, taps = 27, 256, 5 * 5 * 96
    assert CONV2_MACS == side * side * channels * taps
    assert work.conv_macs(_rows("alexnet")["conv2"]) == CONV2_MACS


@pytest.mark.parametrize("name,flops,params", [
    ("alexnet", 2_272_633_600, 62_378_344),
    ("cifar10", 21_611_904, 116_906),
])
def test_frame_flops_and_parameters(name, flops, params):
    rows = _rows(name)
    assert work.flops_per_frame(spec.config(name)) == flops
    assert sum(work.weight_elems(r) for r in rows.values()) == params


def test_alexnet_groups_at_batch_16():
    groups = work.group_work(spec.config("alexnet"), 16)
    gflop = {g["name"]: g["flops"] / 1e9 for g in groups["conv"]}
    assert gflop == pytest.approx({"conv1+pool1+norm1": 3.393441792,
                                   "conv2+pool2+norm2": 14.345183232,
                                   "conv3+conv4+conv5+pool5": 16.747610112})
    fc6 = groups["fc"][0]
    # the minimum: the input and output rows once, every weight once
    batch, d_in, d_out = 16, 9216, 4096
    assert fc6["bytes"] == 4 * (batch * d_in + batch * d_out
                                + d_in * d_out + d_out)


def test_ideal_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    groups = [{"flops": 1000.0, "bytes": 10.0},   # 10 s of compute
              {"flops": 10.0, "bytes": 50.0}]     # 5 s of bandwidth
    assert work.ideal_s(groups, peaks) == pytest.approx(15.0)


def test_a_layer_outside_every_group_is_refused():
    cfg = spec.config("cifar10")
    cfg["kernels"]["fc"]["groups"] = [["fc1"]]
    with pytest.raises(ValueError, match="exactly one group"):
        work.group_work(cfg, 1)


def tiny_graph() -> dict:
    """A ResNet bottleneck at test size (``testdata/tiny_bottleneck.json``):
    a stem conv, a padded max pool, 1x1 -> grouped 3x3 -> 1x1 with a gain,
    a strided 1x1 projection shortcut, an add with ReLU, a global average
    pool, an fc and a softmax."""
    return json.loads((spec.HERE / "testdata" / "tiny_bottleneck.json")
                      .read_text())


#: the tiny graph by hand, per frame: (FLOPs, weights and biases)
TINY_BY_HAND = {
    "stem": (2 * 16 * 16 * 8 * 3 * 3 * 3, 8 * 3 * 9 + 8),
    "pool0": (8 * 8 * 8 * 3 * 3, 0),              # (16 + 2 - 3)//2 + 1 = 8
    "b1_conv1": (2 * 8 * 8 * 4 * 8, 4 * 8 + 4),
    "b1_conv2": (2 * 4 * 4 * 4 * 2 * 3 * 3, 4 * 2 * 9 + 4),  # 2 in/group
    "b1_conv3": (2 * 4 * 4 * 16 * 4, 16 * 4 + 16),
    "b1_proj": (2 * 4 * 4 * 16 * 8, 16 * 8 + 16),
    "b1_add": (1 * 16 * 4 * 4, 0),                # one add per element
    "gap": (16 * 4 * 4, 0),
    "flatten": (0, 0),
    "fc": (2 * 16 * 10, 16 * 10 + 10),
    "prob": (0, 0),
}


def test_graph_flops_and_weights_by_hand():
    rows = {r["name"]: r for r in work.layer_shapes(tiny_graph())}
    assert rows["b1_add"]["inputs"] == ["b1_conv3", "b1_proj"]
    assert rows["b1_proj"]["inputs"] == ["pool0"]
    assert rows["b1_conv1"]["inputs"] == ["pool0"]
    assert rows["stem"]["inputs"] == ["input"]
    assert rows["b1_conv2"]["weight_shape"] == [4, 2, 3, 3]
    got = {n: (work.layer_flops(r), work.weight_elems(r))
           for n, r in rows.items()}
    assert got == TINY_BY_HAND
    assert work.flops_per_frame(tiny_graph()) == 128_576 == sum(
        f for f, _ in TINY_BY_HAND.values())


def test_graph_group_bytes_by_hand():
    batch, b = 3, 4  # float32 activations and weights
    groups = work.group_work(tiny_graph(), batch)
    got = {g["name"]: (g["flops"], g["bytes"])
           for fam in groups.values() for g in fam}
    flops = {n: f * batch for n, (f, _) in TINY_BY_HAND.items()}
    frame, pool0, block, gap = 3 * 16 * 16, 8 * 8 * 8, 16 * 4 * 4, 16
    assert got == {
        "stem+pool0": (flops["stem"] + flops["pool0"],
                       b * batch * (frame + pool0) + b * 224),
        # reads the shortcut from outside: pool0 and b1_proj in, the sum out
        "b1_conv1+b1_conv2+b1_conv3+b1_add": (
            flops["b1_conv1"] + flops["b1_conv2"] + flops["b1_conv3"]
            + flops["b1_add"],
            b * batch * (pool0 + block + block) + b * (36 + 76 + 80)),
        "b1_proj": (flops["b1_proj"], b * batch * (pool0 + block) + b * 144),
        "gap": (flops["gap"], b * batch * (block + gap)),
        "fc": (flops["fc"], b * batch * (gap + 10) + b * 170),
    }


def test_a_group_reads_a_shared_input_once_and_writes_what_leaves_it():
    cfg = tiny_graph()
    cfg["kernels"]["conv"]["groups"] = [
        ["stem", "pool0"],
        ["b1_conv1", "b1_conv2", "b1_conv3", "b1_proj", "b1_add"], ["gap"]]
    block = work.group_work(cfg, 1)["conv"][1]
    # both branches read pool0: once in, the sum out
    assert block["bytes"] == 4 * (8 * 8 * 8 + 16 * 4 * 4) + 4 * (
        36 + 76 + 80 + 144)
    # a group that ends the network writes its output
    cfg["layers"] = cfg["layers"][:-1]  # no softmax: fc's is the output
    assert work.group_work(cfg, 1)["fc"][0]["bytes"] == \
        4 * (16 + 10) + 4 * 170


def _broken(change):
    cfg = tiny_graph()
    change({layer["name"]: layer for layer in cfg["layers"]})
    return cfg


@pytest.mark.parametrize("change,match", [
    (lambda ls: ls["b1_add"].update(inputs=["b1_conv3", "b1_conv1"]),
     "one shape"),
    (lambda ls: ls["b1_add"].update(inputs=["b1_conv3"]), "2 or more"),
    (lambda ls: ls["b1_proj"].update(inputs=["b1_add"]), "not earlier"),
    (lambda ls: ls["b1_conv1"].update(inputs=["pool0", "stem"]),
     "reads one input"),
    (lambda ls: ls["b1_conv2"].update(groups=3), "groups"),
    (lambda ls: ls["b1_proj"].update(name="stem"), "used twice"),
    (lambda ls: ls["gap"].update(name="input"), "reserved"),
])
def test_a_malformed_graph_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        work.layer_shapes(_broken(change))
