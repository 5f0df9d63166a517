"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed on CPU-only hosts too: describing a
``v5e:2x2`` topology lets Mosaic lower and compile each kernel at the
real widths of the default plans — alexnet's three fused conv dispatches
and fc6 at batch 16 (paper §6.2), and lenet5's conv1+pool1.  Interpret
mode never checks Mosaic's tiling, layout or VMEM rules; these compiles
do, and nothing runs.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  The kernels are called with ``interpret=False`` because the
backend the wrappers see here is the CPU.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.matmul_fused import ops as mm_ops
from repro.kernels.pool2d import ops as pool_ops

BATCH = 16
_LRN = dict(lrn_n=5, lrn_alpha=1e-4, lrn_beta=0.75, lrn_k=1.0)
_POOL = dict(pool_kernel=(3, 3), pool_stride=(2, 2))
_SIMD = "advanced_simd_8"  # the engine's default method


def _alexnet_conv1(x, w, b):
    return conv_ops.conv2d(x, w, b, (4, 4), (0, 0), True, method=_SIMD,
                           interpret=False, **_POOL, **_LRN)


def _alexnet_conv2(x, w, b):
    return conv_ops.conv2d(x, w, b, (1, 1), (2, 2), True, method=_SIMD,
                           interpret=False, **_POOL, **_LRN)


def _alexnet_chain(x, w3, b3, w4, b4, w5, b5):
    return conv_ops.conv2d_chain(
        x, (w3, w4, w5), (b3, b4, b5), ((1, 1),) * 3, ((1, 1),) * 3,
        (True,) * 3, method=_SIMD, interpret=False, **_POOL)


def _alexnet_fc6(x, w, b):
    return mm_ops.matmul_fused(x, w, b, act="relu", interpret=False)


def _lenet5_conv1(x, w, b):
    return conv_ops.conv2d(x, w, b, (1, 1), (0, 0), False, method=_SIMD,
                           interpret=False, pool_kernel=(2, 2),
                           pool_stride=(2, 2))


def _resnet50_join(x, w, b, shortcut):
    return conv_ops.conv2d_residual(x, w, b, shortcut, (1, 1), (0, 0), True,
                                    method=_SIMD, interpret=False)


def _resnet50_conv1(x, w, b):
    return conv_ops.conv2d(x, w, b, (2, 2), (3, 3), True, method=_SIMD,
                           interpret=False)


def _resnet50_strided(pad):
    def conv(x, w, b):
        return conv_ops.conv2d(x, w, b, (2, 2), pad, pad != (0, 0),
                               method=_SIMD, interpret=False)
    return conv


def _resnet50_pool1(x):
    return pool_ops.pool2d(x, (3, 3), (2, 2), "max", padding=(1, 1),
                           interpret=False)


def _resnet50_pool5(x):
    return pool_ops.pool2d(x, (7, 7), (1, 1), "avg", interpret=False)


#: dispatch -> (function, operand shapes)
CASES = {
    "alexnet-conv1+pool1+norm1": (
        _alexnet_conv1, [(BATCH, 3, 227, 227), (96, 3, 11, 11), (96,)]),
    "alexnet-conv2+pool2+norm2": (
        _alexnet_conv2, [(BATCH, 96, 27, 27), (256, 96, 5, 5), (256,)]),
    "alexnet-conv3+conv4+conv5+pool5": (
        _alexnet_chain, [(BATCH, 256, 13, 13), (384, 256, 3, 3), (384,),
                         (384, 384, 3, 3), (384,), (256, 384, 3, 3),
                         (256,)]),
    "alexnet-fc6": (_alexnet_fc6, [(BATCH, 9216), (9216, 4096), (4096,)]),
    "lenet5-conv1+pool1": (
        _lenet5_conv1, [(BATCH, 1, 28, 28), (20, 1, 5, 5), (20,)]),
    # ResNet-50: the residual joins at both ends of the network, the
    # 224x224 stem's width-tap dispatch, the padded max pool, strided
    # convs over 2 and 8 lane tiles of channels, the global pool
    "resnet50-s1b1_c+s1b1_add": (
        _resnet50_join, [(BATCH, 64, 56, 56), (256, 64, 1, 1), (256,),
                         (BATCH, 256, 56, 56)]),
    "resnet50-s4b3_c+s4b3_add": (
        _resnet50_join, [(BATCH, 512, 7, 7), (2048, 512, 1, 1), (2048,),
                         (BATCH, 2048, 7, 7)]),
    "resnet50-conv1": (
        _resnet50_conv1, [(BATCH, 3, 224, 224), (64, 3, 7, 7), (64,)]),
    "resnet50-pool1": (_resnet50_pool1, [(BATCH, 64, 112, 112)]),
    "resnet50-s3b1_b": (
        _resnet50_strided((1, 1)), [(BATCH, 256, 28, 28), (256, 256, 3, 3),
                                    (256,)]),
    "resnet50-s4b1_proj": (
        _resnet50_strided((0, 0)), [(BATCH, 1024, 14, 14),
                                    (2048, 1024, 1, 1), (2048,)]),
    "resnet50-pool5": (_resnet50_pool5, [(BATCH, 2048, 7, 7)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()  # a Mosaic kernel, not XLA
    lowered.compile()  # raises what the chip's compiler would refuse


def _narrow_entry_slices(hlo_text):
    """The entry computation's ``slice`` results whose minor dimension,
    by their layout, holds 8 elements or fewer."""
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    narrow = []
    for m in re.finditer(r"= f32\[([\d,]+)\]\{([\d,]+)[:}][^\n]* slice\(",
                         entry):
        dims = [int(d) for d in m.group(1).split(",")]
        minor = int(m.group(2).split(",")[0])
        if dims[minor] <= 8:
            narrow.append(m.group(0))
    return narrow


def test_conv1_operand_is_built_without_lane_padded_slices(one_chip):
    """alexnet's conv1 dispatch builds its width-tap operand from the
    NCHW frame: no slice with 3 or 8 channels on the lane axis, each
    tiled to 128 lanes in HBM, and a temp near the 106 MB operand (the
    NHWC route took 1.63 GB)."""
    fn, shapes = CASES["alexnet-conv1+pool1+norm1"]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 256 * 2**20
    assert _narrow_entry_slices(compiled.as_text()) == []


def test_narrow_slices_are_found_in_hand_made_hlo():
    hlo = ("HloModule m\n\nENTRY %main {\n"
           "  %s.0 = f32[16,235,55,8]{3,2,1,0:T(8,128)} slice(%p), x\n"
           "  %s.1 = f32[16,8,235,55]{1,3,2,0:T(8,128)} slice(%p), x\n"
           "  %s.2 = f32[16,8,235,55]{3,2,1,0:T(8,128)} slice(%p), x\n"
           "  %f = f32[16,235,55,128]{3,2,1,0} fusion(%s.2), x\n}\n")
    found = _narrow_entry_slices(hlo)
    assert len(found) == 2 and "s.2" not in "".join(found)


def test_bucket_program_puts_each_kernel_under_its_plan_step(one_chip,
                                                             monkeypatch):
    """cifar10's whole bucket program, compiled for v5e: every Pallas
    kernel and every other operation of a step carries that step's
    scope in its ``op_name``, so ``op_steps`` maps the kernels the trace
    names (``conv2d.3``, ``matmul_fused.2``) to their plan steps."""
    from repro.core.engine import CNNEngine
    from repro.core.netdefs import NETWORKS

    for mod in (conv_ops, mm_ops, pool_ops):
        monkeypatch.setattr(mod, "interpret_default", lambda: False)
    eng = CNNEngine(NETWORKS["cifar10"](), use_pallas=True)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(eng.init, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((BATCH, *eng.net.input_shape), jnp.float32,
                             sharding=one_chip)
    steps = eng.op_steps(params, x)
    kernels = {n: label for n, label in steps.items()
               if n.startswith(("conv2d.", "matmul_fused."))}
    assert sorted(kernels.values()) == [
        "conv1+pool1", "conv2+pool2", "conv3+pool3", "fc1", "fc2"]
    assert steps["x.1"] == ""
    assert {s.label for s in eng.plan().steps} == set(steps.values()) - {""}


def test_the_residual_join_is_its_own_kernel_in_the_program(one_chip):
    fn, shapes = CASES["resnet50-s1b1_c+s1b1_add"]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(r"%conv2d_residual\.\d+ = \S+ custom-call\(", text)


def test_resnet50_bucket_program_maps_its_joins_to_their_steps(
        one_chip, monkeypatch):
    """ResNet-50's whole bucket program at batch 16, compiled for v5e:
    each residual join's kernel (``conv2d_residual.N``) carries its
    step's scope, so ``op_steps`` names every join."""
    from repro.core.engine import CNNEngine
    from repro.core.netdefs import NETWORKS

    for mod in (conv_ops, mm_ops, pool_ops):
        monkeypatch.setattr(mod, "interpret_default", lambda: False)
    eng = CNNEngine(NETWORKS["resnet50"](), use_pallas=True)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(eng.init, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((BATCH, *eng.net.input_shape), jnp.float32,
                             sharding=one_chip)
    steps = eng.op_steps(params, x)
    joins = sorted(label for n, label in steps.items()
                   if n.startswith("conv2d_residual."))
    want = sorted(s.label for s in eng.plan().steps if s.kind == "residual")
    assert len(want) == 16 and joins == want
    kernels = [n for n in steps if re.match(
        r"(conv2d|conv2d_chain|conv2d_residual|pool2d|matmul_fused)\.\d+$",
        n)]
    assert len(kernels) == sum(s.kind not in ("flatten", "softmax")
                               for s in eng.plan().steps)
