"""Branching networks through the plan IR: tensors by name, residual
joins in the single-conv SIMD cell's epilogue, padded pools, init gain,
and the lane-tiled view that lets a strided conv read more than one
lane tile of channels.  The kernels run in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles the same dispatches for a v5e."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis.verifier import verify_plan
from repro.core import cost, deploy
from repro.core.engine import CNNEngine
from repro.core.fusion import plan_fusion
from repro.core.methods import Method
from repro.core.netdefs import NETWORKS, LayerSpec, NetworkDef
from repro.core.plan import compile_plan
from repro.kernels.conv2d import kernels as K
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.pool2d.ops import pool2d
from repro.kernels.pool2d.ref import pool2d_ref

SIMD = Method.ADVANCED_SIMD_8
HI = lax.Precision.HIGHEST


def _conv(name, src, out, k, stride=1, relu=True, gain=1.0):
    return LayerSpec("conv", name, out_channels=out, kernel=(k, k),
                     stride=(stride, stride), padding=(k // 2, k // 2),
                     relu=relu, inputs=(src,), gain=gain)


def _block_net(proj=True, c_relu=False):
    """A stem, one bottleneck ending in an add (with a strided
    projection, or the block's input as the shortcut), a global pool
    and an fc."""
    layers = [_conv("stem", "input", 8, 3),
              _conv("a", "stem", 8, 1), _conv("b", "a", 8, 3),
              _conv("c", "b", 8, 1, relu=c_relu, gain=0.2)]
    shortcut = "stem"
    if proj:
        layers.append(_conv("proj", "stem", 8, 1, relu=False))
        shortcut = "proj"
    layers += [LayerSpec("add", "add", relu=True, inputs=("c", shortcut)),
               LayerSpec("pool", "gap", kernel=(8, 8), pool_kind="avg",
                         inputs=("add",)),
               LayerSpec("flatten", "flat", inputs=("gap",)),
               LayerSpec("fc", "fc", out_channels=4, inputs=("flat",)),
               LayerSpec("softmax", "prob", inputs=("fc",))]
    return NetworkDef("block", (3, 8, 8), 4, tuple(layers))


def _ref_conv(x, w, b, stride, pad):
    y = lax.conv_general_dilated(x, w, stride, [(pad[0],) * 2, (pad[1],) * 2],
                                 dimension_numbers=("NCHW", "OIHW", "NCHW"),
                                 precision=HI)
    return y + b[None, :, None, None]


# -- the plan IR: named tensors, residual joins -----------------------------


def test_a_join_fuses_into_its_conv_on_the_pallas_path_only():
    net = _block_net()
    pallas = compile_plan(net, method=SIMD, use_pallas=True)
    assert [s.label for s in pallas.steps] == [
        "stem", "a+b", "proj", "c+add", "gap", "flat", "fc", "prob"]
    join = pallas.steps[3]
    assert join.kind == "residual" and join.relu
    assert join.inputs == ("b", "proj") and join.output == "add"
    xla = compile_plan(net, method=SIMD, use_pallas=False)
    # the conv that carries the join on the chip joins no chain here
    # either; the add is a step of its own
    assert [s.label for s in xla.steps] == [
        "stem", "a+b", "c", "proj", "add", "gap", "flat", "fc", "prob"]
    assert xla.steps[4].kind == "add"
    assert xla.steps[4].inputs == ("c", "proj")


@pytest.mark.parametrize("why", ["conv_relu", "no_fuse", "seq_ref"])
def test_a_join_stays_two_steps_where_the_epilogue_cannot_run_it(why):
    # the shortcut is the stem, which has readers besides the add
    net = _block_net(proj=False, c_relu=why == "conv_relu")
    kw = dict(use_pallas=True, method=SIMD)
    if why == "no_fuse":
        kw["per_layer_fuse"] = {"add": False}
    if why == "seq_ref":
        kw["per_layer_methods"] = {"c": Method.SEQ_REF}
    plan = compile_plan(net, **kw)
    kinds = {s.label: s.kind for s in plan.steps}
    assert kinds["add"] == "add" and "residual" not in kinds.values()


def test_a_join_moves_to_the_other_conv_it_reads():
    plan = compile_plan(_block_net(c_relu=True), method=SIMD,
                        use_pallas=True)
    join = next(s for s in plan.steps if s.kind == "residual")
    assert join.label == "proj+add" and join.inputs == ("stem", "c")


def test_the_planner_never_fuses_across_a_tensor_with_two_readers():
    # conv a's output feeds both conv b and the add; b's feeds the pool
    # and the add: neither a chain a+b nor a pool epilogue may form
    net = NetworkDef("fork", (8, 8, 8), 4, (
        _conv("a", "input", 8, 3),
        _conv("b", "a", 8, 3),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2), inputs=("b",)),
        LayerSpec("add", "add", inputs=("a", "b")),
        LayerSpec("relu", "r", inputs=("add",)),
    ))
    for use_pallas in (False, True):
        plan = compile_plan(net, method=SIMD, use_pallas=use_pallas,
                            verify=False)
        assert [s.label for s in plan.steps] == ["a", "b", "p", "add", "r"]
        assert plan.steps[3].inputs == ("a", "b")
    items = plan_fusion(net, method_for=lambda n: SIMD, residual=True)
    assert [getattr(it, "name", None) for it in items] == [
        "a", "b", "p", "add", "r"]


def test_a_relu_is_folded_only_into_the_step_it_alone_reads():
    net = NetworkDef("fold", (8, 8, 8), 4, (
        _conv("a", "input", 8, 3, relu=False),
        LayerSpec("relu", "r", inputs=("a",)),
        LayerSpec("add", "add", inputs=("a", "r")),
    ))
    plan = compile_plan(net, method=Method.SEQ_REF)
    assert [s.kind for s in plan.steps] == ["conv", "relu", "add"]


def test_strided_convs_only_lead_a_chain():
    net = NetworkDef("s", (8, 16, 16), 4, (
        _conv("a", "input", 8, 1), _conv("b", "a", 8, 3, stride=2),
        _conv("c", "b", 8, 3)))
    plan = compile_plan(net, method=SIMD, use_pallas=True)
    assert [s.label for s in plan.steps] == ["a", "b+c"]


def test_the_paper_networks_plan_as_before():
    """lenet5's, cifar10's and alexnet's plans, over every method x
    fuse x backend: step kinds, labels, methods, oh_blocks, folded
    ReLUs, shapes and the executed group geometry, against the
    digest of the plans before the graph IR."""
    rows = {}
    for name in ("lenet5", "cifar10", "alexnet"):
        for method in Method:
            for fuse in (False, True):
                for use_pallas in (False, True):
                    plan = compile_plan(NETWORKS[name](), method=method,
                                        fuse=fuse, use_pallas=use_pallas)
                    rows[f"{name}/{method.value}/{fuse}/{use_pallas}"] = [
                        [s.kind, s.label,
                         s.method.value if s.method else None, s.oh_block,
                         s.relu, list(s.in_shape), list(s.out_shape)]
                        for s in plan.steps] + [plan.fusion_report()]
    assert [r[:2] for r in rows["alexnet/advanced_simd_8/True/True"][:-1]] \
        == [["fused", "conv1+pool1+norm1"], ["fused", "conv2+pool2+norm2"],
            ["chain", "conv3+conv4+conv5+pool5"], ["flatten", "flatten"],
            ["fc", "fc6"], ["fc", "fc7"], ["fc", "fc8"], ["softmax", "prob"]]
    digest = hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()
    assert digest == ("f9460c106fb0d9baf6fbc75b9d29716d"
                      "140c0c1dd4350a4646007e2fa88f19ed")


def test_resnet50_plan_joins_every_block_in_a_residual_cell():
    plan = CNNEngine(NETWORKS["resnet50"](), use_pallas=True).plan(True)
    joins = [s for s in plan.steps if s.kind == "residual"]
    assert len(joins) == 16
    assert joins[0].label == "s1b1_c+s1b1_add"
    assert joins[0].inputs == ("s1b1_b", "s1b1_proj")
    assert joins[1].inputs == ("s1b2_b", "s1b1_add")
    report = {r["group"]: r for r in plan.fusion_report()}
    assert report["s1b1_c+s1b1_add"]["shortcut"] == "s1b1_proj"
    assert report["s4b3_c+s4b3_add"]["shortcut"] == "s4b2_add"
    kinds = [s.kind for s in plan.steps]
    assert "add" not in kinds
    assert sum(k in ("conv", "fused", "chain", "residual", "pool")
               for k in kinds) == 42
    assert verify_plan(plan) == []


def test_execute_keeps_every_named_output_for_collect():
    net = _block_net()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    acts = {}
    eng.forward(params, jnp.ones((2, 3, 8, 8)), collect=acts)
    assert set(acts) == {layer.name for layer in net.layers}
    want = jnp.maximum(acts["c"] + acts["proj"], 0.0)
    np.testing.assert_allclose(acts["add"], want, rtol=0, atol=1e-6)


def test_an_unknown_input_is_refused():
    net = NetworkDef("bad", (3, 8, 8), 4, (
        _conv("a", "input", 8, 3), _conv("b", "nowhere", 8, 3)))
    with pytest.raises(ValueError, match="nowhere"):
        compile_plan(net)


# -- the verifier resolves named producers ---------------------------------


def test_v101_a_shortcut_of_the_wrong_shape():
    plan = compile_plan(_block_net(), method=SIMD, use_pallas=True,
                        verify=False)
    steps = list(plan.steps)
    i = next(k for k, s in enumerate(steps) if s.label == "proj")
    steps[i] = dataclasses.replace(steps[i], out_shape=(16, 8, 8))
    bad = dataclasses.replace(plan, steps=tuple(steps))
    found = [f for f in verify_plan(bad) if f.step.endswith("c+add")]
    assert [f.rule for f in found] == ["V101"]
    assert "proj" in found[0].detail


def test_v102_a_reader_of_an_undefined_name():
    plan = compile_plan(_block_net(), method=SIMD, use_pallas=True,
                        verify=False)
    steps = list(plan.steps)
    i = next(k for k, s in enumerate(steps) if s.label == "c+add")
    steps[i] = dataclasses.replace(steps[i], inputs=("b", "projj"))
    bad = dataclasses.replace(plan, steps=tuple(steps))
    found = verify_plan(bad)
    assert {f.rule for f in found} == {"V102"}
    assert "projj" in found[0].detail


# -- kernels ----------------------------------------------------------------


@pytest.mark.parametrize("method", ["advanced_simd_8", "basic_simd"])
@pytest.mark.parametrize("relu", [False, True])
def test_conv2d_residual_matches_conv_plus_shortcut(method, relu):
    """Several row bands and (advanced) two oc tiles, one padded: the
    shortcut is read in the band and tile of the block it joins."""
    kx, kw_, kb, ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(kx, (2, 16, 11, 9))
    w = 0.2 * jax.random.normal(kw_, (160, 16, 3, 3))
    b = jax.random.normal(kb, (160,))
    shortcut = jax.random.normal(ks, (2, 160, 11, 9))
    out = conv_ops.conv2d_residual(x, w, b, shortcut, (1, 1), (1, 1), relu,
                                   method=method, oh_block=4)
    want = _ref_conv(x, w, b, (1, 1), (1, 1)) + shortcut
    if relu:
        want = jnp.maximum(want, 0.0)
    np.testing.assert_allclose(out, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("method", ["advanced_simd_8", "basic_simd"])
@pytest.mark.parametrize("c,k", [(256, 1), (136, 3)])
def test_a_strided_conv_reads_many_lane_tiles(method, c, k):
    """A strided conv over more than one lane tile of channels runs on
    the lane-tiled view of its input (channels padded to whole tiles
    where they do not fill one)."""
    kx, kw_ = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (2, c, 9, 10))
    w = 0.1 * jax.random.normal(kw_, (24, c, k, k))
    b = jnp.linspace(-1.0, 1.0, 24)
    pad = (k // 2, k // 2)
    out = conv_ops.conv2d(x, w, b, (2, 2), pad, True, method=method,
                          oh_block=2)
    want = jnp.maximum(_ref_conv(x, w, b, (2, 2), pad), 0.0)
    np.testing.assert_allclose(out, want, rtol=0, atol=2e-5)


def test_basic_simd_blocks_oc_where_full_width_weights_bust_vmem():
    # ResNet-50's 1024->2048 projection: 8 MiB of weights at full width
    assert K.resolve_basic_ocb(2048, 7, 14, 1024, 1, 1, 2) == 1024
    assert K.resolve_basic_ocb(256, 56, 56, 64, 1, 1, 1) == 256
    kx, kw_ = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (1, 1024, 3, 3))
    w = 0.03 * jax.random.normal(kw_, (2048, 1024, 1, 1))
    b = jnp.zeros((2048,))
    out = conv_ops.conv2d(x, w, b, (2, 2), (0, 0), False,
                          method="basic_simd")
    np.testing.assert_allclose(out, _ref_conv(x, w, b, (2, 2), (0, 0)),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("kind", ["max", "avg"])
def test_a_padded_pool_matches_the_reference(kind):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 9, 11)) - 2.0
    out = pool2d(x, (3, 3), (2, 2), kind, padding=(1, 1), oh_block=2)
    want = pool2d_ref(x, (3, 3), (2, 2), kind, padding=(1, 1))
    assert out.shape == want.shape == (2, 12, 5, 6)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-6)
    if kind == "avg":  # a corner window holds 4 of 9 frame values
        np.testing.assert_allclose(
            out[0, 0, 0, 0], jnp.sum(x[0, 0, :2, :2]) / 9.0, rtol=1e-6)


# -- parameters, cost, deployment -------------------------------------------


def test_init_multiplies_a_layers_draw_by_its_gain():
    net = _block_net()
    plain = NetworkDef(net.name, net.input_shape, net.num_classes,
                       tuple(dataclasses.replace(s, gain=1.0)
                             for s in net.layers))
    key = jax.random.PRNGKey(11)
    got = CNNEngine(net).init(key)
    want = CNNEngine(plain).init(key)
    for name in want:
        scale = 0.2 if name == "c" else 1.0
        np.testing.assert_array_equal(got[name]["w"],
                                      scale * want[name]["w"])


def test_cost_counts_the_join_bytes():
    plan = compile_plan(_block_net(), method=SIMD, use_pallas=True)
    xla = compile_plan(_block_net(), method=SIMD, use_pallas=False)
    by = {s.label: s for s in cost.plan_cost(plan, batch=2).steps}
    by_xla = {s.label: s for s in cost.plan_cost(xla, batch=2).steps}
    act = 2 * 8 * 8 * 8 * 4  # one [2, 8, 8, 8] fp32 tensor
    assert by_xla["add"].flops == 0 and by_xla["add"].hbm_bytes == 3 * act
    assert by["c+add"].kind == "residual"
    assert by["c+add"].hbm_bytes == by_xla["c"].hbm_bytes + act
    assert by["c+add"].flops == by_xla["c"].flops


def test_a_branching_network_round_trips_through_deploy(tmp_path):
    net = _block_net()
    eng = CNNEngine(net)
    params = eng.init(jax.random.PRNGKey(2))
    deploy.save_model(tmp_path / "m", net, params)
    loaded, lparams, _ = deploy.load_model(tmp_path / "m")
    assert loaded == net
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 8, 8))
    np.testing.assert_array_equal(eng.forward(params, x),
                                  CNNEngine(loaded).forward(lparams, x))
