"""GQA attention block: train/prefill (flash kernel) and decode (cached).

Cache layout [B, T, Hkv, D] keeps the sequence dim second so long-context
decode can shard it over the *model* axis (see parallel/sharding.py) — the
softmax over a sharded T lowers to cheap per-(b,h) all-reduces instead of
an all-gather of the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..parallel.sharding import constrain, shards_of
from .layers import apply_rope, init_linear


def init_attention(key: jax.Array, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": init_linear(ks[1], (d_model, n_kv_heads * head_dim), dtype),
        "wv": init_linear(ks[2], (d_model, n_kv_heads * head_dim), dtype),
        "wo": init_linear(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(params: dict, x: jax.Array, n_heads: int, n_kv_heads: int,
                 head_dim: int):
    b = x.shape[0]
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, -1, n_heads, head_dim)
    k = k.reshape(b, -1, n_kv_heads, head_dim)
    v = v.reshape(b, -1, n_kv_heads, head_dim)
    return q, k, v


def attention_block(params: dict, x: jax.Array, *, n_heads: int,
                    n_kv_heads: int, head_dim: int, rope_theta: float,
                    positions: jax.Array | None = None,
                    return_kv: bool = False, force_chunked: bool = False):
    """Full-sequence causal attention (training / prefill).  With
    ``return_kv`` also returns the rotated K/V [B,S,Hkv,D] for cache fill."""
    bsz, s, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    # kernels expect [B, H, S, D]
    out = ops.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                              v.swapaxes(1, 2), causal=True,
                              force_chunked=force_chunked)
    out = out.swapaxes(1, 2).reshape(bsz, s, n_heads * head_dim)
    out = out @ params["wo"]
    if return_kv:
        return out, (k, v)
    return out


# Rows of a cache block written at once.  On the TPU a cache whose head
# dim does not fill the 128 lanes (stablelm-3b's 80) is laid out with T
# minor, where it needs no padding.  A one-row update, and a scatter of
# rows, make XLA lay the whole stack out D-minor around the layer loop
# instead, relaying it out on the way in and out; an update of the
# lane-aligned block of rows that holds the row keeps the stack's layout.
_ROW_BLOCK = 128


def _write_row(stack: jax.Array, row: jax.Array, layer: jax.Array,
               length: jax.Array) -> jax.Array:
    """Write ``row`` [B,Hkv,D] into ``stack`` [L,B,T,Hkv,D] at
    (``layer``, b, ``length[b]``) for each b, leaving every other row as it
    was; with the stack donated, in place.  A row at or past T is dropped."""
    b, t = stack.shape[1], stack.shape[2]
    row = row.astype(stack.dtype)
    if shards_of(t, "model") > 1:
        # T sharded (SP): a write at a traced position lowers to a gather
        # of the cache, so each shard selects its own rows of the layer,
        # pinned sequence-sharded so that the softmax over T lowers to
        # per-(b,h) all-reduces instead of a full-cache reshard/gather
        onehot = jax.nn.one_hot(length, t, dtype=row.dtype)      # [B,T]
        cache = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        cache = constrain(cache + onehot[:, :, None, None] * row[:, None],
                          ("dp", "model", None, None))
        return jax.lax.dynamic_update_index_in_dim(stack, cache, layer, 0)
    n = min(_ROW_BLOCK, t)
    for i in range(b):
        start = jnp.minimum(length[i] // n * n, t - n)
        at = (layer, i, start, 0, 0)
        block = jax.lax.dynamic_slice(stack, at, (1, 1, n) + stack.shape[3:])
        hit = (jnp.arange(n) == length[i] - start)[None, None, :, None, None]
        stack = jax.lax.dynamic_update_slice(
            stack, jnp.where(hit, row[i], block), at)
    return stack


def attention_decode(params: dict, x: jax.Array, cache: dict,
                     layer: jax.Array, *, n_heads: int, n_kv_heads: int,
                     head_dim: int, rope_theta: float
                     ) -> tuple[jax.Array, dict]:
    """One-token decode for layer ``layer`` of a cache stack.  x: [B,1,d];
    cache: {"k","v": [L,B,T,Hkv,D], "length": [L,B]} -> (out [B,1,d], the
    stack with this layer's new K/V row written at its length and its
    length advanced).  Nothing else of the stack is rewritten."""
    bsz = x.shape[0]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    length = cache["length"][layer]                      # [B]
    pos = length[:, None]                                # [B,1]
    q = apply_rope(q, pos, rope_theta)
    k = apply_rope(k, pos, rope_theta)

    k_all = _write_row(cache["k"], k[:, 0], layer, length)
    v_all = _write_row(cache["v"], v[:, 0], layer, length)
    lengths = length + 1

    out = ops.decode_attention(
        q[:, 0], jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False),
        jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False), lengths)
    out = constrain(out, ("dp", None, None))
    out = out.reshape(bsz, 1, n_heads * head_dim)
    new_cache = {"k": k_all, "v": v_all,
                 "length": cache["length"].at[layer].set(lengths)}
    return out @ params["wo"], new_cache


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.float32) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }
