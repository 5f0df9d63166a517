"""``work.py`` against hand-worked layers of the committed
configurations."""
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
