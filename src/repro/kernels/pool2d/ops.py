"""jit'd public wrapper for the Pallas pooling kernels.

Accepts NCHW (the deploy format), swaps to NHWC so channels sit on the
128-lane minor axis (same dimension swapping as the SIMD conv methods),
pads channels to the sublane multiple, dispatches to the oh-band-tiled
Pallas kernel, and swaps back.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.layout import nchw_to_nhwc, nhwc_to_nchw, pad_axis
from repro.kernels.common import interpret_default
from repro.kernels.pool2d import kernels as K

SUBLANES = 8  # channel padding multiple (mirrors conv2d.ops)


@partial(jax.jit, static_argnames=("kernel", "stride", "kind", "relu",
                                   "oh_block", "interpret", "padding"))
def pool2d(x, kernel=(2, 2), stride=(2, 2), kind: str = "max",
           relu: bool = False, oh_block: int = None,
           interpret: bool = None, padding=(0, 0)):
    """x: [N, C, H, W]; VALID windows over the frame padded by
    ``padding`` rows and columns on each side: -inf for max, zeros that
    the avg divisor counts."""
    interp = interpret_default() if interpret is None else interpret
    xh = nchw_to_nhwc(x)
    if padding != (0, 0):
        py, px = padding
        xh = jnp.pad(xh, ((0, 0), (py, py), (px, px), (0, 0)),
                     constant_values=-jnp.inf if kind == "max" else 0.0)
    xh, orig_c = pad_axis(xh, 3, SUBLANES)  # pad value 0 never crosses
    out = K.pool2d_nhwc(xh, kernel, stride, kind, relu,  # channel lanes
                        oh_block=oh_block, interpret=interp)
    return nhwc_to_nchw(out[..., :orig_c])
