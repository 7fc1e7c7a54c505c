"""What the family references share: the model's sizes, its weights made
again from the seed, the blocks in float32, and the runner that turns a
batch of token rows into logit gaps.

Weights.  The program draws every weight from one ``PRNGKey(seed)``:
``split(key, 4)`` gives the embedding, head, shared-block and layer keys,
the layer key is split once per stacked layer (in plan order, the shared
block not counted), and each layer key is split 4 ways (block, FFN, ...).
A matrix is ``normal(key, shape) * scale`` cast to the served dtype, with
``scale = fan_in ** -0.5`` unless stated.  The reference makes the same
draws, layer by layer, so it holds one layer at a time and never reads
the program's arrays.

Equations.  They follow the blocks as this repository writes them, in
float32 at ``Precision.HIGHEST``; each family module lists where they
depart from the published model.

Families.  A family module's ``plan(m, seed)`` lists the blocks in order.
A block is named (``"attn"``, ``"mamba"``: the blocks below) or is a
layer that the module makes itself with ``layer(weights, block)``, from
its own weight draws and equations; such a block may read the embedding
output ``x0`` and any key of the configuration's ``model`` group
(``Model.get``), and the control rounds its weights as it rounds these.

Control.  ``quant="int8"`` rounds every weight matrix to int8 with one
scale per output column (per row for the embedding) before use, and
``quant="fp8"`` to float8 e4m3 with the same scales: the weight-only
steps below bfloat16 that a faster decode would be tempted to take.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
CONV_W = 4


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a configuration file's ``model`` group."""
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    dtype: str
    head_dim: int = 0
    act: str = "swiglu"
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    ssm_state: int = 0
    mamba_head_dim: int = 64
    shared_attn_every: int = 0
    # every other key of the group, as sorted (key, value) pairs with lists
    # made tuples, so that the sizes stay hashable (a static argument)
    extra: tuple = ()

    @classmethod
    def from_dict(cls, d: dict) -> "Model":
        names = {f.name for f in dataclasses.fields(cls)} - {"extra"}
        return cls(**{k: v for k, v in d.items() if k in names},
                   extra=tuple(sorted((k, _frozen(v)) for k, v in d.items()
                                      if k not in names)))

    def get(self, key: str, default: Any = None) -> Any:
        """The group's ``key``, a field or not; ``default`` where the
        group has no such key."""
        if key != "extra" and key in self.__dataclass_fields__:
            return getattr(self, key)
        return dict(self.extra).get(key, default)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


def _frozen(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    if isinstance(v, list):
        return tuple(_frozen(x) for x in v)
    return v


# -- weights -----------------------------------------------------------------

def _normal(key, shape, dtype, scale: Optional[float] = None):
    scale = shape[0] ** -0.5 if scale is None else scale
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def root_keys(seed: int):
    """(embed, head, shared block, layers) keys, as the program splits."""
    return jax.random.split(jax.random.PRNGKey(seed), 4)


def layer_keys(k_layers, n: int):
    return jax.random.split(k_layers, max(n, 1))


def attn_weights(key, m: Model) -> dict:
    """One attention + SwiGLU block: ``ks[0]`` feeds q/k/v/o, ``ks[1]``
    the FFN; the norms are ones."""
    dt = jnp.dtype(m.dtype)
    d, hd = m.d_model, m.hd
    ks = jax.random.split(key, 4)
    ka = jax.random.split(ks[0], 4)
    kf = jax.random.split(ks[1], 3)
    if m.act != "swiglu":
        raise ValueError(f"reference has no FFN for act={m.act!r}")
    return {
        "wq": _normal(ka[0], (d, m.n_heads * hd), dt),
        "wk": _normal(ka[1], (d, m.n_kv_heads * hd), dt),
        "wv": _normal(ka[2], (d, m.n_kv_heads * hd), dt),
        "wo": _normal(ka[3], (m.n_heads * hd, d), dt),
        "w_gate": _normal(kf[0], (d, m.d_ff), dt),
        "w_up": _normal(kf[1], (d, m.d_ff), dt),
        "w_down": _normal(kf[2], (m.d_ff, d), dt),
    }


def mamba_weights(key, m: Model) -> dict:
    """One Mamba-2 block: ``ks[0]`` is split 7 ways; ``dt_bias`` and
    ``a_log`` are zeros and the norms ones."""
    dt = jnp.dtype(m.dtype)
    d, di = m.d_model, m.n_heads * m.mamba_head_dim
    ks = jax.random.split(jax.random.split(key, 4)[0], 7)
    return {
        "wx": _normal(ks[0], (d, di), dt),
        "wz": _normal(ks[1], (d, di), dt),
        "wb": _normal(ks[2], (d, m.ssm_state), dt),
        "wc": _normal(ks[3], (d, m.ssm_state), dt),
        "wdt": _normal(ks[4], (d, m.n_heads), dt),
        "conv_w": _normal(ks[5], (CONV_W, di), dt, scale=0.2),
        "w_out": _normal(ks[6], (di, d), dt),
    }


def embed_weight(k_embed, m: Model):
    return _normal(k_embed, (m.vocab, m.d_model), jnp.dtype(m.dtype),
                   scale=0.02)


def head_weight(k_head, m: Model):
    return _normal(k_head, (m.d_model, m.vocab), jnp.dtype(m.dtype))


def to_f32(w: dict, quant: Optional[str]) -> dict:
    return {k: _quantize(v.astype(jnp.float32), quant, axis=0)
            for k, v in w.items()}


def _quantize(w, quant: Optional[str], axis: int):
    if quant is None:
        return w
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if quant == "int8":
        return jnp.clip(jnp.round(w / scale), -top, top) * scale
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# -- float32 blocks ---------------------------------------------------------

def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, eps: float):
    """RMSNorm with unit weight (every norm weight the program makes is
    ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta: float):
    """Rotary embedding on the whole head, halves rotated (not
    interleaved).  x: [L, H, D] at positions 0..L-1."""
    n, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_block(w: dict, x, m: Model):
    """Pre-norm causal attention then pre-norm SwiGLU FFN, each added to
    the residual.  x: [n, L, d]."""
    n, length, _ = x.shape
    hd = m.hd
    group = m.n_heads // m.n_kv_heads
    h = rms_norm(x, m.rms_eps)
    q = mm(h, w["wq"]).reshape(n, length, m.n_heads, hd)
    k = mm(h, w["wk"]).reshape(n, length, m.n_kv_heads, hd)
    v = mm(h, w["wv"]).reshape(n, length, m.n_kv_heads, hd)
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one(qkv):
        qs, ks, vs = qkv
        qs, ks = rope(qs, m.rope_theta), rope(ks, m.rope_theta)
        ks = jnp.repeat(ks, group, axis=1)
        vs = jnp.repeat(vs, group, axis=1)
        s = jnp.einsum("shd,thd->hst", qs, ks, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", p, vs, precision=HI)

    o = jax.lax.map(one, (q, k, v)).reshape(n, length, m.n_heads * hd)
    x = x + mm(o, w["wo"])
    h = rms_norm(x, m.rms_eps)
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])


def mamba_block(w: dict, x, m: Model):
    """Pre-norm Mamba-2 block as the repository writes it: causal
    depthwise conv of width 4 and SiLU on the x branch, scalar decay per
    head ``a = -softplus(x W_dt)`` (``dt_bias`` 0, ``A = -exp(0)``), B and
    C shared by all heads, the SSD recurrence
    ``h_t = exp(a_t) h_{t-1} + x_t B_t^T``, ``y_t = h_t C_t`` in its
    closed form, a gated RMSNorm ``norm(y * silu(z))`` and the output
    projection."""
    n, length, _ = x.shape
    hh, hd = m.n_heads, m.mamba_head_dim
    h = rms_norm(x, m.rms_eps)
    xs = mm(h, w["wx"])
    z = mm(h, w["wz"])
    b = mm(h, w["wb"])
    c = mm(h, w["wc"])
    a = -jax.nn.softplus(mm(h, w["wdt"]))                # [n, L, H]
    pad = jnp.pad(xs, ((0, 0), (CONV_W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + length] * w["conv_w"][i] for i in range(CONV_W))
    xs = jax.nn.silu(conv).reshape(n, length, hh, hd)
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one(args):
        xh, ah, bh, ch = args
        acum = jnp.cumsum(ah, axis=0)                    # [L, H]
        seg = acum[:, None, :] - acum[None, :, :]        # [t, u, H]
        decay = jnp.exp(jnp.where(causal[..., None], seg, -jnp.inf))
        cb = jnp.einsum("tn,un->tu", ch, bh, precision=HI)
        return jnp.einsum("tuh,tu,uhd->thd", decay, cb, xh, precision=HI)

    y = jax.lax.map(one, (xs, a, b, c)).reshape(n, length, hh * hd)
    y = rms_norm(y * jax.nn.silu(z), 1e-5)
    return x + mm(y, w["w_out"])


# -- runner -----------------------------------------------------------------

def _gaps(x, head, targets, m: Model):
    """Per position: the best logit less the logit of each target set's
    token, and the best token.  x: [n, L, d]; targets: [k, n, L]."""
    def one(args):
        xs, ts = args
        logits = mm(rms_norm(xs, m.rms_eps), head)        # [L, V]
        best = logits.max(axis=-1)
        picked = jnp.take_along_axis(logits[None], ts[..., None], -1)[..., 0]
        return best[None] - picked, logits.argmax(axis=-1)

    gaps, top = jax.lax.map(one, (x, jnp.swapaxes(targets, 0, 1)))
    return jnp.swapaxes(gaps, 0, 1), top


def run_layers(plan, m: Model, seed: int, tokens, targets,
               quant: Optional[str] = None):
    """Runs a family's ``plan`` (a list of ``(block, key)``, ``block`` a
    name of ``_LAYERS`` or a layer made by ``layer``) over token rows
    [n, L] and returns, as numpy, the logit gaps [k, n, L] of the target
    token sets [k, n, L] and the reference's own best tokens [n, L].  A
    block whose equations take ``x0`` gets the embedding output."""
    import numpy as np
    k_embed, k_head, _, _ = root_keys(seed)
    x = x0 = _embed_rows(k_embed, jnp.asarray(tokens, jnp.int32), m, quant)
    for block, key in plan:
        run = _LAYERS[block] if isinstance(block, str) else block
        if "x0" in inspect.signature(run).parameters:
            x = run(key, x, m, quant, x0)
        else:
            x = run(key, x, m, quant)
    gaps, top = _head_gaps(k_embed, k_head, x,
                           jnp.asarray(targets, jnp.int32), m, quant)
    return np.asarray(gaps), np.asarray(top)


def layer(weights, block):
    """A plan's block from a weight maker ``weights(key, m) -> dict`` and
    float32 equations ``block(w, x, m)``, or ``block(w, x, m, x0)`` for
    one that reads the embedding output: the weights are drawn in the
    served dtype, then made float32 and, under the control, rounded
    (``to_f32``).  Jitted, with the sizes and ``quant`` static."""
    if "x0" in inspect.signature(block).parameters:
        def run(key, x, m, quant, x0):
            return block(to_f32(weights(key, m), quant), x, m, x0)
    else:
        def run(key, x, m, quant):
            return block(to_f32(weights(key, m), quant), x, m)
    return jax.jit(run, static_argnums=(2, 3))


def _embed(k_embed, tokens, m, quant):
    return _embed_f32(k_embed, m, quant)[tokens]


def _embed_f32(k_embed, m, quant):
    return _quantize(embed_weight(k_embed, m).astype(jnp.float32), quant,
                     axis=1)


def _head(k_embed, k_head, x, targets, m, quant):
    """The gaps under the output head: the embedding matrix transposed
    where the group sets ``tie_embeddings``, as the program does, else the
    head's own draw."""
    if m.get("tie_embeddings", False):
        head = _embed_f32(k_embed, m, quant).T
    else:
        head = _quantize(head_weight(k_head, m).astype(jnp.float32), quant,
                         0)
    return _gaps(x, head, targets, m)


_LAYERS = {
    "attn": layer(attn_weights, attention_block),
    "mamba": layer(mamba_weights, mamba_block),
}
_embed_rows = jax.jit(_embed, static_argnums=(2, 3))
_head_gaps = jax.jit(_head, static_argnums=(4, 5))
