"""Fused bias+activation matmul Pallas kernel (the paper's FC acceleration).

CNNdroid §4.4 computes several output elements per thread so each loaded
operand is reused; on the MXU the analogue is a [bm, bn] output tile per
grid cell — one loaded x-tile is reused across the whole 128-wide output
block, and the bias+activation epilogue runs while the tile is still in
VMEM (the zero-cost ReLU of Fig. 5).

Grid: (M/bm, N/bn, K/bk) with K innermost-sequential; the output BlockSpec
ignores the K index so the same VMEM tile accumulates across K steps
(canonical Pallas accumulation idiom).  fp32 accumulation regardless of
input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import ACC_DTYPE, ACC_PRECISION


def _act(y, act: str):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "silu":
        return y * (1.0 / (1.0 + jnp.exp(-y)))
    if act == "gelu":
        return 0.5 * y * (1.0 + jnp.tanh(0.7978845608028654 *
                                         (y + 0.044715 * y ** 3)))
    return y


def _kernel(x_ref, w_ref, b_ref, o_ref, *, act: str, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(ACC_DTYPE), w_ref[...].astype(ACC_DTYPE),
        preferred_element_type=jnp.float32,
        precision=ACC_PRECISION,
    )

    @pl.when(k == nk - 1)
    def _epilogue():  # fused bias + activation — no extra HBM pass
        y = o_ref[...]
        if b_ref is not None:
            y = y + b_ref[...].astype(ACC_DTYPE)
        o_ref[...] = _act(y, act)


def matmul_fused_pallas(
    x, w, b=None, act: str = "none",
    bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
):
    """x: [M, K]; w: [K, N]; b: [N] or None -> [M, N] fp32."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    if b is not None and pn:
        b = jnp.pad(b, (0, pn))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    nk = Kp // bk

    kernel = functools.partial(_kernel, act=act, nk=nk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        # 2-D bias: Mosaic tiles a 1-D block unlike XLA's 1-D layout
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b.reshape(1, Np))
    else:
        kernel = functools.partial(_kernel, act=act, nk=nk)

        def kernel2(x_ref, w_ref, o_ref, *, act=act, nk=nk):
            _kernel(x_ref, w_ref, None, o_ref, act=act, nk=nk)

        kernel = kernel2

    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*args)
    return out[:M, :N]
