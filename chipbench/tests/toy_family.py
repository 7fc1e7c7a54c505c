"""A toy family reference, for the tests: what a new family's module
supplies, written as new code only.

Each of ``n_layers`` blocks reads the hidden state and the embedding
output ``x0`` side by side: ``x + gain * tanh([norm(x), x0] W_in) W_out``,
with ``W_in`` [2 d_model, toy_width].  ``toy_width`` and ``toy_gain`` are
keys of the ``model`` group that ``common.Model`` has no field for; the
output head is the embedding transposed (``tie_embeddings``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import common


def weights(key, m: common.Model) -> dict:
    dt = jnp.dtype(m.dtype)
    width = m.get("toy_width")
    k_in, k_out = jax.random.split(key)
    return {
        "w_in": (jax.random.normal(k_in, (2 * m.d_model, width))
                 * 0.1).astype(dt),
        "w_out": (jax.random.normal(k_out, (width, m.d_model))
                  * 0.1).astype(dt),
    }


def block(w: dict, x, m: common.Model, x0):
    h = jnp.concatenate([common.rms_norm(x, m.rms_eps), x0], axis=-1)
    return x + m.get("toy_gain", 1.0) * common.mm(
        jnp.tanh(common.mm(h, w["w_in"])), w["w_out"])


TOY = common.layer(weights, block)


def plan(m: common.Model, seed: int) -> list:
    _, _, _, k_layers = common.root_keys(seed)
    keys = common.layer_keys(k_layers, m.n_layers)
    return [(TOY, keys[i]) for i in range(m.n_layers)]
