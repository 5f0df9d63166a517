"""Spatial (oh-band) tiling correctness for the Pallas conv ladder.

All three conv methods vs ``conv2d_ref`` across stride/padding combos,
non-multiple-of-8 channel counts, and frames large enough to force
multiple oh-tiles (interpret mode) — including a 512×512×64 frame whose
padded activations exceed the VMEM budget the seed kernel assumed.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.conv2d import kernels as K
from repro.kernels.conv2d.kernels import VMEM_BUDGET_BYTES, auto_oh_block
from repro.kernels.conv2d.ops import conv2d as conv2d_pallas
from repro.kernels.conv2d.ref import conv2d_ref

METHODS = ("basic_parallel", "basic_simd", "advanced_simd_128")


def _case(n, c, h, w_, oc, k, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, c, h, w_),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (oc, c, k, k)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(seed + 2), (oc,))
    return x, w, b


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pad", [0, 1, 2])
def test_conv2d_stride_padding_sweep(method, stride, pad):
    x, w, b = _case(2, 5, 14, 14, 7, 3)  # 5 in / 7 out: not multiples of 8
    ref = conv2d_ref(x, w, b, (stride, stride), (pad, pad), relu=True)
    out = conv2d_pallas(x, w, b, (stride, stride), (pad, pad), relu=True,
                        method=method, interpret=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


@pytest.mark.parametrize("method", ("basic_simd", "advanced_simd_128"))
@pytest.mark.parametrize("oh_block", [1, 3, 8, 64])
def test_conv2d_explicit_oh_blocks(method, oh_block):
    """Every band size — including ragged last tiles (17 % 3 != 0) and
    bands larger than the frame — matches the untiled reference."""
    x, w, b = _case(1, 6, 17, 13, 10, 3)
    ref = conv2d_ref(x, w, b, (1, 1), (1, 1), relu=True)
    out = conv2d_pallas(x, w, b, (1, 1), (1, 1), relu=True, method=method,
                        oh_block=oh_block, interpret=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


@pytest.mark.parametrize("method", ("basic_simd", "advanced_simd_128"))
@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_multi_tile_strided(method, stride):
    """Multiple oh-tiles with stride: each band's input offset is
    stride-aware (band t starts at t*oh_block*stride input rows)."""
    x, w, b = _case(1, 4, 40, 20, 6, 5)
    ref = conv2d_ref(x, w, b, (stride, stride), (2, 2), relu=False)
    out = conv2d_pallas(x, w, b, (stride, stride), (2, 2), relu=False,
                        method=method, oh_block=7, interpret=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


def test_conv2d_large_frame_multi_tile():
    """The acceptance shape: a 512×512×64 NHWC frame.  The whole padded
    frame (514×514×64 fp32 ≈ 67 MB) cannot be staged in VMEM; the auto
    heuristic must split it into several oh-bands, and the result must
    still match the reference."""
    x, w, b = _case(1, 64, 512, 512, 16, 3, seed=7)
    w = w * 0.5  # keep values O(1) so 1e-4 abs tolerance is meaningful
    ref = conv2d_ref(x, w, b, (1, 1), (1, 1), relu=True)
    # the frame the seed kernel would have staged whole:
    frame_bytes = 514 * 514 * 64 * 4
    assert frame_bytes > VMEM_BUDGET_BYTES
    # the geometry the kernel executes: oc_block clamps to min(128, oc)=16
    ohb = auto_oh_block(512, 512, 514, 64, 3, 3, 1, 16)
    assert ohb < 512  # the heuristic actually tiles this frame
    out = conv2d_pallas(x, w, b, (1, 1), (1, 1), relu=True,
                        method="advanced_simd_128", interpret=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


def test_auto_oh_block_monotone_and_bounded():
    """Auto bands stay within the frame, and shrinking the budget never
    grows the band."""
    prev = None
    for budget in (64 * 2**20, 8 * 2**20, 1 * 2**20, 64 * 1024):
        ohb = auto_oh_block(256, 256, 258, 64, 3, 3, 1, 128, budget=budget)
        assert 1 <= ohb <= 256
        if prev is not None:
            assert ohb <= prev
        prev = ohb
    # small frames fall back to a single whole-frame tile under a big budget
    assert auto_oh_block(13, 13, 15, 8, 3, 3, 1, 128) == 13


def _nhwc_width_taps(x_nhwc, wmat, rows, px, kh, kw, sx, ow):
    """The width-tap operand built the older way, through NHWC: pad the
    rows and width, cut ``kw`` stride-``sx`` slices with C on the minor
    axis, join them along C and pad to the lane width."""
    lanes = K.LANES
    xp = jnp.pad(x_nhwc, ((0, 0), rows, (px, px), (0, 0)))
    n, hp, _, c = xp.shape
    taps = [jax.lax.slice(xp, (0, 0, j, 0),
                          (n, hp, j + (ow - 1) * sx + 1, c), (1, 1, sx, 1))
            for j in range(kw)]
    xu = jnp.pad(jnp.concatenate(taps, axis=-1),
                 ((0, 0), (0, 0), (0, 0), (0, lanes - kw * c)))
    oc = wmat.shape[1]
    wu = jnp.pad(wmat.reshape(kh, kw * c, oc),
                 ((0, 0), (0, lanes - kw * c), (0, 0)))
    return xu, wu.reshape(kh * lanes, oc)


#: the convs the packing rule takes (kw > 1, kw * Cp <= 128), cut down:
#: name -> (x shape, w shape, conv2d keywords, top rows beyond the pad)
_PACKED = {
    # alexnet conv1 (11x11/4 + pool 3/2 + LRN): 4 pooled bands, bottom rows
    "alexnet-conv1": ((2, 3, 67, 67), (16, 3, 11, 11),
                      dict(stride=(4, 4), padding=(0, 0), relu=True,
                           pool_kernel=(3, 3), pool_stride=(2, 2),
                           lrn_n=5, lrn_alpha=1e-4, lrn_beta=0.75,
                           lrn_k=1.0, oh_block=5), 0),
    # cifar10 conv1 (5x5/1 pad 2 + pool 3/2) on the carry cell: the
    # zero prepad rows above the frame
    "cifar10-conv1": ((2, 3, 32, 32), (32, 3, 5, 5),
                      dict(stride=(1, 1), padding=(2, 2), relu=True,
                           pool_kernel=(3, 3), pool_stride=(2, 2),
                           pool_carry=True, oh_block=8), 5),
    # lenet5 conv1 (5x5/1, one channel + pool 2/2)
    "lenet5-conv1": ((2, 1, 28, 28), (20, 1, 5, 5),
                     dict(stride=(1, 1), padding=(0, 0), relu=False,
                          pool_kernel=(2, 2), pool_stride=(2, 2)), 0),
}


@pytest.mark.parametrize("name", sorted(_PACKED))
def test_width_tap_operand_matches_the_nhwc_route(name, monkeypatch):
    """The packed operand built from the NCHW frame is the one the NHWC
    route builds, byte for byte, and so is the dispatch's output."""
    xs, ws, kwargs, prepad = _PACKED[name]
    x, w, b = (jax.random.normal(jax.random.PRNGKey(i), s, jnp.float32)
               for i, s in enumerate((xs, ws, ws[:1])))
    calls = []
    packer = K._pack_width_taps

    def spy(*args):
        calls.append((args, packer(*args)))
        return calls[-1][1]

    # unjitted: each call traces the packer it finds
    run = partial(conv2d_pallas.__wrapped__, x, w, b,
                  method="advanced_simd_8", interpret=True, **kwargs)
    monkeypatch.setattr(K, "_pack_width_taps", spy)
    out = run()
    monkeypatch.setattr(K, "_pack_width_taps", _nhwc_width_taps)
    ref = run()
    (args, (xu, wu)), = calls
    assert args[2][0] == kwargs["padding"][0] + prepad
    xr, wr = _nhwc_width_taps(*args)
    assert xu.shape == xr.shape and xu.shape[-1] == K.LANES
    assert bool(jnp.array_equal(xu, xr)) and bool(jnp.array_equal(wu, wr))
    assert bool(jnp.array_equal(out, ref))
