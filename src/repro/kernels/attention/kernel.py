"""Flash attention Pallas TPU kernel (forward).

Beyond-paper kernel required by the 32k prefill shapes: online-softmax
attention with O(block²) VMEM.  Structure:

  grid = (batch·heads, q_tiles, kv_tiles)   kv innermost, sequential
  q block   [bq, hd]      (VMEM, reused across all kv steps — the paper's
                           "load once, reuse" argument applied to queries)
  k/v block [bk, hd]
  scratch   m [bq], l [bq], acc [bq, hd] fp32 — persists across kv steps

Causal/sliding-window masking is applied per block from iota; blocks that
are entirely masked are skipped with ``pl.when`` so the FLOPs match the
true masked cost.  The jnp twin (``repro.nn.attention.chunked_attention``)
is the oracle and the autodiff path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import ACC_DTYPE

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, cap, causal, window, bq, bk, nk, skv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    k_hi = k_lo + bk - 1
    visible = True
    if causal:
        visible = jnp.asarray(k_lo <= q_hi)
    if window > 0:
        visible = jnp.logical_and(visible, k_hi > q_lo - window)

    @pl.when(visible if not isinstance(visible, bool) else True)
    def _step():
        q = q_ref[...].astype(ACC_DTYPE)  # [bq, hd]
        k = k_ref[...].astype(ACC_DTYPE)  # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        s = s * scale
        if cap and cap > 0.0:
            s = cap * jnp.tanh(s / cap)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < skv  # kv padding
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        row_ok = m_new > NEG_INF / 2
        p = jnp.exp(s - m_new[:, None]) * row_ok[:, None]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_ref[...].astype(ACC_DTYPE), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v, *, causal=True, window=0, attn_softcap=0.0, scale=None,
    bq: int = 512, bk: int = 512, interpret: bool = False,
):
    """q: [b, sq, h, hd]; k/v: [b, skv, h, hd] (kv heads pre-expanded).

    Returns [b, sq, h, hd]."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq = min(bq, sq)
    bk = min(bk, skv)
    pq, pk = (-sq) % bq, (-skv) % bk
    # layout: fold (b, h) into the leading grid axis
    qt = jnp.pad(q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd),
                 ((0, 0), (0, pq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3).reshape(b * h, skv, hd),
                 ((0, 0), (0, pk), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3).reshape(b * h, skv, hd),
                 ((0, 0), (0, pk), (0, 0)))
    nq, nk = (sq + pq) // bq, (skv + pk) // bk

    kern = functools.partial(
        _kernel, scale=scale, cap=attn_softcap, causal=causal,
        window=window, bq=bq, bk=bk, nk=nk, skv=skv,
    )
    out = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, bk, hd), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((None, bk, hd), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :sq].reshape(b, h, sq, hd).transpose(0, 2, 1, 3)