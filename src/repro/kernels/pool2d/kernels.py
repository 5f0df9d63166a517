"""Pallas TPU pooling kernels: max/avg over NHWC, oh-band tiled.

Same grid-over-frames structure as the conv ladder: grid cell
``(frame, oh-tile)``; each cell loads only the input-row band its output
band needs — ``(oh_block-1)*stride + KH`` rows including the halo — via a
stride-aware element-offset (``pl.Element``) block dim, exactly the
PR 1 conv plumbing.  This replaces the engine's bare ``reduce_window``
("accelerated on mobile CPU" in the paper) with a VMEM-resident kernel so
pooling joins the ladder and can be fused as a conv epilogue.

``pool_band`` is the shared in-VMEM pooling primitive: it reduces an
``[H, W, C]`` band ref to fp32 ``[ph, pw, C]`` with unrolled window
loops.  The fused conv kernels in ``repro.kernels.conv2d.kernels`` stage
their conv accumulator in a VMEM scratch ref and pool it there, so the
intermediate activation never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import ACC_DTYPE

from repro.kernels.conv2d.kernels import (
    LANES,
    VMEM_BUDGET_BYTES,
    VMEM_LIMIT_BYTES,
    _band_rows,
    auto_oh_block,
    band_intervals,
)


def _out_size(size, k, stride):
    return (size - k) // stride + 1


def pool_band(x_ref, ph, pw, pkh, pkw, psy, psx, kind: str):
    """Pool an ``[H, W, C]`` band ref down to fp32 ``[ph, pw, C]``.

    Unrolled over the (small, static) pool window; a strided ``pl.ds``
    load picks each window position's contribution (Mosaic lowers
    strided ref loads, not strided slices of a loaded value), so the
    reduction is pure VPU work on data already in VMEM.
    """
    c = x_ref.shape[2]
    if kind == "max":
        acc = jnp.full((ph, pw, c), -jnp.inf, jnp.float32)
    elif kind == "avg":
        acc = jnp.zeros((ph, pw, c), jnp.float32)
    else:
        raise ValueError(kind)
    for i in range(pkh):
        for j in range(pkw):
            win = x_ref[pl.ds(i, ph, stride=psy), pl.ds(j, pw, stride=psx),
                        :].astype(ACC_DTYPE)  # [ph, pw, C]
            if kind == "max":
                acc = jnp.maximum(acc, win)
            else:
                acc = acc + win
    if kind == "avg":
        acc = acc / float(pkh * pkw)
    return acc


def auto_oh_block_pool(oh, ow, wp, c, kh, sy,
                       budget: int = VMEM_BUDGET_BYTES,
                       itemsize: int = 4) -> int:
    """Largest pooled-output row band whose working set (input band +
    output block, fp32) fits ``budget``.

    Delegates to the conv tiler's candidate walk with the weight and
    oc-block terms zeroed (``oc_block=0``) and the single ``[rows, C]``
    staging slice (``im2col=False``) standing in for the pooled output —
    one copy of the VMEM-fitting heuristic for the whole ladder.
    """
    return auto_oh_block(oh, ow, wp, c, kh, 1, sy, oc_block=0,
                         budget=budget, itemsize=itemsize, im2col=False)


def _pool2d_kernel(x_ref, o_ref, *, kh, kw, sy, sx, kind, relu):
    # x_ref: [BAND, WP, C] (input-row band); o_ref: [OH_BLK, OW, C]
    ohh, oww, _ = o_ref.shape
    acc = pool_band(x_ref, ohh, oww, kh, kw, sy, sx, kind)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.astype(o_ref.dtype)


def pool2d_nhwc(x_nhwc, kernel=(2, 2), stride=(2, 2), kind: str = "max",
                relu: bool = False, oh_block=None, interpret: bool = False):
    """VALID pooling over [N, H, W, C], output-row-band grid."""
    n, h, wd, c = x_nhwc.shape
    kh, kw = kernel
    sy, sx = stride
    oh, ow = _out_size(h, kh, sy), _out_size(wd, kw, sx)
    if oh < 1 or ow < 1:
        raise ValueError(f"pool window {kernel} larger than input {h}x{wd}")
    if oh_block is None:
        ohb = auto_oh_block_pool(oh, ow, wd, c, kh, sy)
    else:
        ohb = max(1, min(oh_block, oh))
    n_tiles = -(-oh // ohb)
    band = _band_rows(ohb, kh, sy)
    row_step = ohb * sy
    # pad the bottom so the last (possibly ragged) band stays in bounds;
    # the surplus pooled rows only read pad and are sliced off below
    _, in_iv = band_intervals(n_tiles, ohb, oh, row_step, band)
    hp_need = in_iv[-1][0] + band
    # channel blocks of at most one lane tile: Mosaic lowers the strided
    # window loads only from a block whose minor dim fits one
    cb = min(c, LANES)
    pad_c = (-c) % cb
    if hp_need > h or pad_c:
        x_nhwc = jnp.pad(x_nhwc, ((0, 0), (0, max(0, hp_need - h)), (0, 0),
                                  (0, pad_c)))
    kern = functools.partial(_pool2d_kernel, kh=kh, kw=kw, sy=sy, sx=sx,
                             kind=kind, relu=relu)
    out = pl.pallas_call(
        kern,
        grid=(n, n_tiles, (c + pad_c) // cb),
        in_specs=[
            # element-offset indexing: bands overlap by the KH-sy halo rows
            pl.BlockSpec((None,
                          pl.Element(band), pl.Element(wd), pl.Element(cb)),
                         lambda i, t, k: (i, t * row_step, 0, k * LANES)),
        ],
        out_specs=pl.BlockSpec((None, ohb, ow, cb),
                               lambda i, t, k: (i, t, 0, k)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * ohb, ow, c + pad_c),
                                       x_nhwc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(x_nhwc)
    return out[:, :oh, :, :c]
