"""jit'd public wrapper for the conv2d kernel ladder.

Accepts NCHW/OIHW (the deploy format), performs the dimension swap +
channel padding host-side (the Fig. 5 "CPU idle time" work), dispatches to
the method's Pallas kernel, and swaps back.

``oh_block`` (SIMD methods only) sets the spatial tile: the output height
is processed in bands of ``oh_block`` rows so each grid cell stages only
the input-row band it needs (halo included) instead of the whole padded
frame.  ``None`` lets ``kernels.auto_oh_block`` pick the largest band that
fits the VMEM budget — required for frames (e.g. 512×512) whose padded
activations exceed VMEM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.layout import (
    nchw_to_nhwc,
    nhwc_to_nchw,
    oihw_to_hwio,
    pad_axis,
)
from repro.kernels.common import interpret_default
from repro.kernels.conv2d import kernels as K
from repro.kernels.conv2d.ref import conv2d_ref

SUBLANES = 8  # channel padding multiple (paper's "divisible by 4", on TPU 8/128)


@partial(jax.jit, static_argnames=("stride", "padding", "relu", "method",
                                   "oh_block", "interpret", "pool_kernel",
                                   "pool_stride", "pool_kind", "pool_relu",
                                   "lrn_n", "lrn_alpha", "lrn_beta", "lrn_k",
                                   "pool_carry", "lrn_oc_block"))
def conv2d(x, w, b, stride=(1, 1), padding=(0, 0), relu=False,
           method: str = "advanced_simd_128", oh_block: int = None,
           interpret: bool = None, pool_kernel=None, pool_stride=None,
           pool_kind: str = "max", pool_relu: bool = False,
           lrn_n: int = None, lrn_alpha: float = 1e-4,
           lrn_beta: float = 0.75, lrn_k: float = 1.0,
           pool_carry: bool = None, lrn_oc_block: bool = None):
    """x: [N, C, H, W]; w: [OC, C, KH, KW]; b: [OC].

    ``pool_kernel``/``pool_stride`` (SIMD methods only) fuse a VALID
    max/avg pooling epilogue into the conv kernel — the super-layer path:
    the conv activation never leaves VMEM and only the pooled band is
    written.  ``relu`` applies between conv and pool, ``pool_relu`` after
    the pool.  ``lrn_n`` (requires ``pool_kernel``) extends the epilogue
    with channel-axis LRN over the in-VMEM pooled band
    (``engine._lrn`` semantics, asymmetric padding for even ``lrn_n``) so
    only the *normalized* band is written — AlexNet's conv→relu→pool→norm
    in one dispatch.

    ``pool_carry`` / ``lrn_oc_block`` (advanced SIMD only) select the
    second-generation fused cells: the sliding-window pool accumulator
    (carry the pool-halo conv rows between bands in VMEM scratch) and the
    two-pass channel-halo LRN cell (oc blocking with window-widened
    weight tiles).  ``None`` = the kernel resolvers decide.
    """
    interp = interpret_default() if interpret is None else interpret
    if method == "basic_parallel":
        if pool_kernel is not None or lrn_n is not None:
            raise ValueError("fused pooling epilogue requires a SIMD method")
        return K.conv2d_basic_parallel(x, w, b, stride, padding, relu,
                                       interpret=interp)
    if lrn_n is not None and pool_kernel is None:
        raise ValueError("fused LRN epilogue requires a fused pool epilogue")
    lrn = (lrn_n, lrn_alpha, lrn_beta, lrn_k) if lrn_n is not None else None
    return _simd(x, w, b, stride, padding, relu, method, oh_block, interp,
                 pool_kernel=pool_kernel, pool_stride=pool_stride,
                 pool_kind=pool_kind, pool_relu=pool_relu, lrn=lrn,
                 pool_carry=pool_carry, lrn_oc_block=lrn_oc_block)


@partial(jax.jit, static_argnames=("stride", "padding", "relu", "method",
                                   "oh_block", "interpret"))
def conv2d_residual(x, w, b, shortcut, stride=(1, 1), padding=(0, 0),
                    relu=False, method: str = "advanced_simd_128",
                    oh_block: int = None, interpret: bool = None):
    """A residual join in one dispatch: ``conv(x) + b + shortcut``, then
    ReLU if ``relu``, in fp32.  ``shortcut`` is ``[N, OC, OH, OW]``, the
    conv's output shape; the SIMD cell reads it in the band and oc tile
    of the output block it writes.  Its own name keeps its kernels
    apart from ``conv2d``'s in a trace (``conv2d_residual.N``)."""
    interp = interpret_default() if interpret is None else interpret
    return _simd(x, w, b, stride, padding, relu, method, oh_block, interp,
                 residual=nchw_to_nhwc(shortcut))


def _simd(x, w, b, stride, padding, relu, method, oh_block, interp, **kw):
    """A SIMD method's dispatch: channel padding + dimension swapping
    (§4.3; C is padded while it is a major axis, where the pad is cheap),
    the method's kernel with the epilogue ``kw`` name, and the swap
    back."""
    xh = nchw_to_nhwc(pad_axis(x, 1, SUBLANES)[0])
    wh = oihw_to_hwio(w)
    wh, _ = pad_axis(wh, 2, SUBLANES)
    if method == "basic_simd":
        kw.pop("pool_carry", None)
        kw.pop("lrn_oc_block", None)
        out = K.conv2d_basic_simd(xh, wh, b, stride, padding, relu,
                                  oh_block=oh_block, interpret=interp, **kw)
    elif method.startswith("advanced_simd"):
        # the paper's 4/8 outputs per thread, raised to the MXU lane tile
        out = K.conv2d_advanced_simd(xh, wh, b, stride, padding, relu,
                                     oc_block=K.LANES, oh_block=oh_block,
                                     interpret=interp, **kw)
    else:
        raise ValueError(method)
    return nhwc_to_nchw(out)


@partial(jax.jit, static_argnames=("strides", "paddings", "relus", "method",
                                   "oh_block", "interpret", "pool_kernel",
                                   "pool_stride", "pool_kind", "pool_relu",
                                   "lrn_n", "lrn_alpha", "lrn_beta", "lrn_k",
                                   "oc_block_final"))
def conv2d_chain(x, ws, bs, strides, paddings, relus,
                 method: str = "advanced_simd_128", oh_block: int = None,
                 interpret: bool = None, pool_kernel=None, pool_stride=None,
                 pool_kind: str = "max", pool_relu: bool = False,
                 lrn_n: int = None, lrn_alpha: float = 1e-4,
                 lrn_beta: float = 0.75, lrn_k: float = 1.0,
                 oc_block_final: int = None):
    """A chain of consecutive convolutions as ONE fused dispatch.

    ``x``: [N, C, H, W]; ``ws``/``bs``: per-stage OIHW weights and biases
    (stage i's input channels = stage i-1's output channels); ``strides``/
    ``paddings``/``relus``: parallel static per-stage tuples.  SIMD
    methods only — the chain cell computes an output-row band of the
    final stage with every intermediate activation (halo included)
    VMEM-resident; ``pool_kernel``(+``lrn_n``) fuse the usual pool/LRN
    tail onto the last stage.  The dimension swap happens once for the
    whole chain, and inter-stage channel padding composes: a stage's
    zero-padded output channels are exact zeros (zero weight columns,
    zero bias), so the next stage's zero-padded input rows consume them
    harmlessly.
    """
    if not method.startswith(("basic_simd", "advanced_simd")):
        raise ValueError("fused conv chain requires a SIMD method")
    if lrn_n is not None and pool_kernel is None:
        raise ValueError("fused LRN epilogue requires a fused pool epilogue")
    lrn = (lrn_n, lrn_alpha, lrn_beta, lrn_k) if lrn_n is not None else None
    interp = interpret_default() if interpret is None else interpret
    im2col = method.startswith("advanced_simd")
    xh = nchw_to_nhwc(x)
    xh, _ = pad_axis(xh, 3, SUBLANES)
    cp = xh.shape[3]
    whs, bps = [], []
    oc_f = ws[-1].shape[0]
    for w, b in zip(ws, bs):
        wh = oihw_to_hwio(w)  # [kh, kw, ci, oc]
        pad_in = cp - wh.shape[2]
        ocp = -(-wh.shape[3] // SUBLANES) * SUBLANES
        wh = jnp.pad(wh, ((0, 0), (0, 0), (0, pad_in),
                          (0, ocp - wh.shape[3])))
        whs.append(wh)
        bps.append(jnp.pad(b, (0, ocp - b.shape[0])))
        cp = ocp
    out = K.conv2d_chain_simd(xh, whs, bps, strides, paddings, relus,
                              im2col=im2col, oh_block=oh_block,
                              interpret=interp, pool_kernel=pool_kernel,
                              pool_stride=pool_stride, pool_kind=pool_kind,
                              pool_relu=pool_relu, lrn=lrn,
                              oc_block_final=oc_block_final)
    return nhwc_to_nchw(out[..., :oc_f])


conv2d_reference = conv2d_ref
