"""Mamba-2 SSD chunked scan (Pallas, TPU target).

The SSD recurrence  h_t = exp(a_t) h_{t-1} + B_t ⊗ x_t,  y_t = h_t C_t
is evaluated chunk-wise (Dao & Gu, arXiv:2405.21060): within a chunk of L
tokens the contribution is a lower-triangular "attention-like" matmul
(MXU-friendly); across chunks a [D, N] state is carried in VMEM scratch
along the sequential chunk grid dimension.

Grid (B, H, S/L); per-chunk work is three small matmuls:
  G   = tril(exp(Acum_t - Acum_u) * (C_t · B_u))   [L, L]
  y   = G @ x  +  exp(Acum) * (C @ h_prevᵀ)        [L, D]
  h'  = exp(A_total) h_prev + (w ⊙ x)ᵀ @ B          [D, N]
With L=128, D=64, N=128 the VMEM footprint is well under 1 MiB.

Mosaic has no cumsum, so the in-chunk prefix sum Acum is a masked lane
reduction over the [L, L] causal mask (exact in f32, unlike an MXU matmul).
The log-decays reach the kernel as a row per (batch, head), [B, H, 1, S]:
a (1, L) block then meets the TPU's (8, 128) tiling rule for any H.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, h_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # [L, D]
    a = a_ref[0, 0].astype(jnp.float32)          # [1, L] log-decay row
    bmat = b_ref[0].astype(jnp.float32)          # [L, N]
    cmat = c_ref[0].astype(jnp.float32)          # [L, N]

    l_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = u_idx <= l_idx
    # inclusive log-decay acum_t = sum_{u<=t} a_u, as a column [L, 1]
    acum = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)
    a_total = acum[chunk - 1:, :]                 # [1, 1]

    # intra-chunk: y_intra[t] = sum_{u<=t} exp(acum_t - acum_u) (C_t·B_u) x_u
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [L, L]
    acum_b = jnp.broadcast_to(acum, (chunk, chunk))
    g = jnp.where(causal, cb * jnp.exp(acum_b - acum_b.T), 0.0)
    y = jax.lax.dot_general(g, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [L, D]

    # inter-chunk carry: y_carry[t] = exp(acum_t) * (C_t · h_prev)
    h_prev = h_ref[...]                           # [D, N]
    y += jnp.exp(acum) * jax.lax.dot_general(
        cmat, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # [L, D]

    # state update: h' = exp(a_total) h_prev + sum_u exp(a_total-acum_u) x_u B_u
    w = jnp.exp(a_total - acum)                   # [L, 1]
    h_ref[...] = jnp.exp(a_total) * h_prev + jax.lax.dot_general(
        x * w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # [D, N]

    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
                    chunk: int = 128, interpret: bool = False) -> jax.Array:
    """x: [B,S,H,D], a: [B,S,H], b,c: [B,S,N] -> y: [B,S,H,D] (see ref.ssd_ref)."""
    bs, s, h, d = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"S={s} not divisible by chunk={chunk}")
    nc = s // chunk

    xt = jnp.swapaxes(x, 1, 2)                    # [B, H, S, D]
    at = jnp.swapaxes(a, 1, 2)[:, :, None, :]     # [B, H, 1, S]

    yt = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bs, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, at, b, c)
    return jnp.swapaxes(yt, 1, 2)
