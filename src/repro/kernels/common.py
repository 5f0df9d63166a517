"""Kernel-wide named constants, and the backend rule for interpret mode.

``ACC_DTYPE`` is the accumulation dtype every Pallas kernel body
computes in: operands are upcast to it on load, partial sums live in
it, and exactly one downcast to ``o_ref.dtype`` happens at the final
store.  Naming the constant (instead of writing ``jnp.float32`` inline)
is what lets two static passes enforce the contract cheaply:

* the repo lint's R007 rule accepts only ``ACC_DTYPE`` or a ref's
  ``.dtype`` as an ``astype`` target inside kernel bodies, and
* the kernel sanitizer's K103 precision-flow lattice resolves the name
  to fp32 when it symbolically executes the bodies.

``ACC_PRECISION`` is the contraction precision of every kernel
``jnp.dot``: Mosaic's default contracts fp32 operands in reduced
precision, so the kernels ask for the fp32 contraction their fp32
reference comparisons assume.

When the quantized int8/fp16 path lands, its kernels get their own
named accumulation constants here and both passes extend by table
entry, not by new pattern-matching.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ACC_DTYPE = jnp.float32
ACC_PRECISION = jax.lax.Precision.HIGHEST


def interpret_default() -> bool:
    """Whether a Pallas kernel called with ``interpret=None`` runs in
    interpret mode: yes on the CPU backend (the test path), no on a TPU.
    Any other backend raises — no device silently falls back to the
    interpreter."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on a TPU, or interpreted on "
                           f"the CPU; the {backend!r} backend is neither")
    return backend == "cpu"
