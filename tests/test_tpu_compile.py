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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.matmul_fused import ops as mm_ops

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
