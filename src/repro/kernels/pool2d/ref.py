"""XLA reference pooling (NCHW reduce_window) — the pre-fusion engine path
and the correctness oracle for the Pallas pool kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pool2d_ref(x, kernel=(2, 2), stride=(2, 2), kind: str = "max",
               relu: bool = False, padding=(0, 0)):
    """x: [N, C, H, W]; windows over the frame padded by ``padding`` on
    each side (-inf for max; zeros, counted in the divisor, for avg)."""
    kh, kw = kernel
    sy, sx = stride
    py, px = padding
    pads = ((0, 0), (0, 0), (py, py), (px, px))
    if kind == "max":
        out = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, kh, kw), (1, 1, sy, sx), pads
        )
    elif kind == "avg":
        out = jax.lax.reduce_window(
            x.astype(jnp.float32), 0.0, jax.lax.add,
            (1, 1, kh, kw), (1, 1, sy, sx), pads
        ) / float(kh * kw)
    else:
        raise ValueError(kind)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype)
