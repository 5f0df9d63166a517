"""jit'd wrapper for the flash-attention kernel (GQA expansion included)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default
from repro.kernels.attention.kernel import flash_attention_pallas
from repro.kernels.attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "attn_softcap",
                                   "scale", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, attn_softcap=0.0,
                    scale=None, interpret: bool = None):
    """q: [b, sq, h, hd]; k/v: [b, skv, kvh, hd] (kv heads auto-expanded)."""
    interp = interpret_default() if interpret is None else interpret
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, attn_softcap=attn_softcap,
        scale=scale, interpret=interp,
    )
