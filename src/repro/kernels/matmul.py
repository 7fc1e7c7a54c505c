"""Blocked MXU matmul Pallas kernel (the paper's compute-intensive node).

Grid (M/bm, N/bn, K/bk); A and B tiles stream HBM->VMEM per BlockSpec; the
f32 accumulator lives in VMEM scratch and is flushed to the output tile on
the last K step.  Block sizes default to 128x128x128 — one MXU-aligned tile
per dimension (multiples of 128 keep the systolic array fully fed); at
(128,128,128)xf32 the VMEM working set is 3 tiles * 64 KiB + 64 KiB
accumulator, far under the ~16 MiB per-core VMEM budget, leaving room for
double buffering by the pipeline emitter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                  bk: int = 128, interpret: bool = False) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch {k} vs {k2}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k},{n}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    return pl.pallas_call(
        _mm_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
                  pl.BlockSpec((bk, bn), lambda i, j, l: (l, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
