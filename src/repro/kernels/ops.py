"""Public kernel entry points.

Each op dispatches to the Pallas kernel on TPU (or when
``REPRO_FORCE_PALLAS_INTERPRET=1`` forces the interpreter for validation)
and to the pure-jnp reference (XLA) otherwise.  Model code and the task
runtime call *these*, never the kernels directly, so the same program runs
on the XLA CPU backend (the tests) and on a TPU.
"""
from __future__ import annotations

import os

import jax

from . import ref
from .copy import copy_pallas
from .flash_attention import flash_attention_pallas
from .matmul import matmul_pallas
from .ssd_scan import ssd_scan_pallas
from .stencil import stencil_pallas


def _use_pallas() -> tuple[bool, bool]:
    """(use_pallas, interpret)."""
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET") == "1":
        return True, True
    platform = jax.default_backend()
    return platform == "tpu", False


def matmul(a: jax.Array, b: jax.Array, **blocks) -> jax.Array:
    use, interp = _use_pallas()
    if use and a.ndim == 2 and not (a.shape[0] % 128 or a.shape[1] % 128
                                    or b.shape[1] % 128):
        return matmul_pallas(a, b, interpret=interp, **blocks)
    return ref.matmul_ref(a, b)


def copy(x: jax.Array, **blocks) -> jax.Array:
    use, interp = _use_pallas()
    if use and x.ndim == 2 and not (x.shape[0] % 8 or x.shape[1] % 128):
        bm = min(blocks.pop("bm", 512), x.shape[0])
        bn = min(blocks.pop("bn", 1024), x.shape[1])
        return copy_pallas(x, bm=bm, bn=bn, interpret=interp, **blocks)
    return ref.copy_ref(x)


def stencil(u: jax.Array, **blocks) -> jax.Array:
    use, interp = _use_pallas()
    if use and u.ndim == 3 and not (u.shape[1] % 8 or u.shape[2] % 128):
        bh = min(blocks.pop("bh", 256), u.shape[1])
        bw = min(blocks.pop("bw", 256), u.shape[2])
        return stencil_pallas(u, bh=bh, bw=bw, interpret=interp, **blocks)
    return ref.stencil_ref(u)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    force_chunked: bool = False, **blocks) -> jax.Array:
    use, interp = _use_pallas()
    s, t, d = q.shape[2], k.shape[2], q.shape[3]
    shapes_ok = s >= 8 and t >= 128 and d % 8 == 0 and s % 8 == 0 and t % 128 == 0
    if use and shapes_ok:
        bq = min(blocks.pop("bq", 256), s)
        bk = min(blocks.pop("bk", 256), t)
        if s % bq == 0 and t % bk == 0:
            return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                          bq=bq, bk=bk, interpret=interp)
    if force_chunked or s * t > (1 << 26):
        # XLA path for truly long sequences (>=32k): bound peak memory at
        # O(bq x T).  At train lengths (4k) the plain path is strictly
        # better under SPMD: the lax.map over q chunks emitted per-chunk KV
        # all-gathers x layers x microbatches (measured: +45% collective
        # bytes on granite-8b train_4k — see EXPERIMENTS.md §Perf).
        return ref.attention_chunked_ref(q, k, v, causal=causal, scale=scale)
    return ref.attention_ref(q, k, v, causal=causal, scale=scale)


def ssd_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
             **blocks) -> jax.Array:
    use, interp = _use_pallas()
    s = x.shape[1]
    if use and s % 128 == 0:
        chunk = min(blocks.pop("chunk", 128), s)
        return ssd_scan_pallas(x, a, b, c, chunk=chunk, interpret=interp)
    return ref.ssd_ref(x, a, b, c)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None) -> jax.Array:
    """Single-token decode: a GEMV chain — XLA already emits the optimal
    fused loop on TPU, so there is no Pallas variant (documented decision)."""
    return ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale)
