"""jit'd public wrapper for the fused matmul kernel.

On TPU the Pallas kernel runs natively; elsewhere (this CPU container)
``interpret=True`` executes the same kernel body op-by-op, and tests assert
allclose against ``ref.matmul_fused_ref``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default
from repro.kernels.matmul_fused.kernel import matmul_fused_pallas
from repro.kernels.matmul_fused.ref import matmul_fused_ref


@partial(jax.jit, static_argnames=("act", "interpret"))
def matmul_fused(x, w, b=None, act: str = "none", interpret: bool = None):
    """y = act(x @ w + b).  Leading dims of x are flattened to M."""
    interp = interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = matmul_fused_pallas(x2, w, b, act=act, interpret=interp)
    return y.reshape(*lead, w.shape[-1]).astype(x.dtype)


matmul_fused_reference = matmul_fused_ref
