"""jit'd wrapper for the WKV6 chunked kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.common import interpret_default
from repro.kernels.wkv6.kernel import wkv6_pallas
from repro.kernels.wkv6.ref import wkv6_reference


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, chunk: int = 32, interpret: bool = None):
    interp = interpret_default() if interpret is None else interpret
    return wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=interp)
