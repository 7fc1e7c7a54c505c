"""Fused causal GQA flash attention (Pallas, TPU target).

Online-softmax formulation: grid (B, Hq, S/bq, T/bk) with the KV dimension
innermost and sequential; running row-max m, normalizer l and the f32
accumulator live in VMEM scratch and persist across KV steps.  GQA is
expressed in the K/V BlockSpec index maps (query head h reads KV head
h // group), so no repeated KV materialization ever exists in HBM or VMEM.

Causal masking aligns the ends of the q and kv windows (T >= S: the last
query row attends to all T keys).  Fully-masked KV blocks are skipped via
``pl.when`` on block-level bounds, saving ~half the work for square causal
attention.

Default blocks (bq, bk) = (256, 256): at D=128 f32, VMEM holds
q (128 KiB) + k + v (2x128 KiB) + acc (128 KiB) + s/p (256 KiB) ≈ 0.8 MiB,
leaving the pipeline room to double-buffer K/V streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, bq, bk, s_len, t_len):
    i = pl.program_id(2)           # q block
    j = pl.program_id(3)           # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    offset = t_len - s_len
    # block-level skip: the first key of this block is beyond the last
    # query position of this q block -> entire block masked out.
    q_pos_max = i * bq + (bq - 1) + offset
    live = jnp.logical_or(jnp.logical_not(causal), j * bk <= q_pos_max)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _flush():
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, scale: float | None = None,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = False) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    bq = min(bq, s)
    bk = min(bk, t)
    if s % bq or t % bk:
        raise ValueError(f"S={s}/T={t} not divisible by blocks ({bq},{bk})")
    scale_v = scale if scale is not None else d ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale_v, causal=causal, bq=bq, bk=bk,
        s_len=s, t_len=t)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
