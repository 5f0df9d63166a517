"""WKV6 (RWKV "Finch") chunked linear-attention Pallas TPU kernel.

The paper's §4.4 argument — load an operand tile once and reuse it across a
whole output block — applied to the *time* axis of an attention-free mixer:
each grid cell owns one (batch·head) stream; the kv-state [e, e] lives in
VMEM scratch across the sequential chunk axis, and each chunk's r/k/v/w
tiles are loaded exactly once for both the intra-chunk pairwise form and
the state update.

  grid = (batch·heads, n_chunks)   chunks sequential
  r/k/v/w blocks [L, e] VMEM;  state scratch [e, e] fp32
  intra-chunk pairwise decay tensor [L, L, e] stays in VMEM (L=32, e=64
  -> 256 KiB fp32)

Matches ``repro.nn.rwkv._wkv6_chunked`` / ``wkv6_reference`` semantics:
  S_t = diag(w_t) S_{t-1} + k_t v_t^T ;  o_t = r_t (S_{t-1} + u ⊙ k_t v_t^T)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import ACC_DTYPE


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *, L):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[...].astype(ACC_DTYPE)  # [L, e]
    k = k_ref[...].astype(ACC_DTYPE)
    v = v_ref[...].astype(ACC_DTYPE)
    w = w_ref[...].astype(ACC_DTYPE)  # log decay, < 0
    u = u_ref[...].astype(ACC_DTYPE)  # [e]
    S = state_ref[...]  # [e_k, e_v]

    cw = jnp.cumsum(w, axis=0)  # inclusive
    cw_prev = cw - w
    # intra-chunk: A[i,j] = sum_e r_i[e] k_j[e] exp(cw_prev_i - cw_j), j < i
    decay = jnp.exp(cw_prev[:, None, :] - cw[None, :, :])  # [L, L, e]
    A = jnp.einsum("ie,ije,je->ij", r, decay, k)
    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    A = jnp.where(li > lj, A, 0.0)
    o = jax.lax.dot(A, v, preferred_element_type=jnp.float32)
    # diagonal bonus: (r_i ⊙ u ⊙ k_i) · v_i
    diag = jnp.sum(r * u[None, :] * k, axis=-1)  # [L]
    o = o + diag[:, None] * v
    # inter-chunk: o_i += (r_i ⊙ exp(cw_prev_i)) @ S
    o = o + jax.lax.dot(r * jnp.exp(cw_prev), S,
                        preferred_element_type=jnp.float32)
    # state update: S' = diag(exp(cw_L)) S + sum_j exp(cw_L - cw_j) k_j v_j^T
    total = cw[-1]  # [e]
    Sc = jax.lax.dot((k * jnp.exp(total[None, :] - cw)).T, v,
                     preferred_element_type=jnp.float32)
    state_ref[...] = S * jnp.exp(total)[:, None] + Sc
    o_ref[...] = o.astype(o_ref.dtype)


def wkv6_pallas(r, k, v, logw, u, *, chunk: int = 32,
                interpret: bool = False):
    """r/k/v/logw: [b, s, h, e]; u: [h, e] -> o [b, s, h, e]."""
    b, s, h, e = r.shape
    L = min(chunk, s)
    pad = (-s) % L
    if pad:
        z = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, z), jnp.pad(k, z), jnp.pad(v, z)
        # padded steps must not change the state: log decay 0 (=> decay 1)
        # and k = 0 give S' = S
        logw = jnp.pad(logw, z)
    sp = s + pad
    nc = sp // L

    def fold(x):  # [b, s, h, e] -> [b*h, s, e]
        return x.transpose(0, 2, 1, 3).reshape(b * h, sp, e)

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(logw)
    uf = jnp.broadcast_to(u[None], (b, h, e)).reshape(b * h, e)

    out = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((None, L, e), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, L, e), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, L, e), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, L, e), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, e), lambda g, c: (g, 0)),
        ],
        out_specs=pl.BlockSpec((None, L, e), lambda g, c: (g, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, e), r.dtype),
        scratch_shapes=[pltpu.VMEM((e, e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    out = out[:, :s].reshape(b, h, s, e).transpose(0, 2, 1, 3)
    return out
