"""How a family reference plugs into the runner: the dense and hybrid
plans give, bit for bit, what the runner gave before families could bring
blocks of their own; and a toy family (``toy_family.py``), with a block of
its own that reads the embedding output and sizes the ``Model`` has no
field for, and a tied head, runs through ``cell.reference_gaps`` as a
direct ``jax.numpy`` computation does."""
import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell
from chipbench.reference import common
from chipbench.tests import toy_family
from chipbench.tests.tiny import TINY, tiny_spec

SEED = 2**31 + 97
HI = jax.lax.Precision.HIGHEST


def _rows(vocab: int, n: int = 3, length: int = 40):
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, vocab, (n, length)).astype(np.int32)
    targets = rng.integers(0, vocab, (2, n, length)).astype(np.int32)
    return tokens, targets


# -- the runner as it was, kept here verbatim as the reference -------------

def _old_attn(key, x, m, quant):
    return common.attention_block(
        common.to_f32(common.attn_weights(key, m), quant), x, m)


def _old_mamba(key, x, m, quant):
    return common.mamba_block(
        common.to_f32(common.mamba_weights(key, m), quant), x, m)


def _old_embed(k_embed, tokens, m, quant):
    return common._quantize(
        common.embed_weight(k_embed, m).astype(jnp.float32), quant,
        axis=1)[tokens]


def _old_head(k_head, x, targets, m, quant):
    head = common._quantize(
        common.head_weight(k_head, m).astype(jnp.float32), quant, 0)
    return common._gaps(x, head, targets, m)


_OLD = {"attn": jax.jit(_old_attn, static_argnums=(2, 3)),
        "mamba": jax.jit(_old_mamba, static_argnums=(2, 3))}
_old_embed_rows = jax.jit(_old_embed, static_argnums=(2, 3))
_old_head_gaps = jax.jit(_old_head, static_argnums=(3, 4))


def _old_run_layers(plan, m, seed, tokens, targets, quant=None):
    k_embed, k_head, _, _ = common.root_keys(seed)
    x = _old_embed_rows(k_embed, jnp.asarray(tokens, jnp.int32), m, quant)
    for block, key in plan:
        x = _OLD[block](key, x, m, quant)
    gaps, top = _old_head_gaps(k_head, x, jnp.asarray(targets, jnp.int32),
                               m, quant)
    return np.asarray(gaps), np.asarray(top)


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_plans_match_the_old_runner_bit_for_bit(family, quant):
    spec = tiny_spec(family)
    spec.config["model"] = dict(spec.config["model"], dtype="bfloat16")
    tokens, targets = _rows(spec.config["model"]["vocab"])
    gaps, top = cell.reference_gaps(spec, SEED, tokens, targets,
                                    quant=quant)
    m = common.Model.from_dict(spec.config["model"])
    old_m = dataclasses.replace(m, extra=())
    ms = cell.model_seed(SEED)
    plan = cell.reference(family).plan(m, ms)
    old_gaps, old_top = _old_run_layers(plan, old_m, ms, tokens, targets,
                                        quant=quant)
    np.testing.assert_array_equal(gaps, old_gaps)
    np.testing.assert_array_equal(top, old_top)


# -- a family that brings its own block ------------------------------------

TOY = dict(TINY["dense"], name="tiny-toy", family="toy", n_layers=3,
           dtype="bfloat16", tie_embeddings=True, toy_width=48,
           toy_gain=0.5)


def test_model_keeps_every_key():
    m = common.Model.from_dict(dict(TOY, hybrid_layer_ids=[6, 11],
                                    nested={"a": [1, 2]}))
    hash(m)
    assert m.get("toy_width") == 48 and m.get("tie_embeddings") is True
    assert m.get("hybrid_layer_ids") == (6, 11)
    assert m.get("nested") == (("a", (1, 2)),)
    assert m.get("d_model") == TOY["d_model"]
    assert m.get("adapter_rank", 0) == 0
    assert common.Model.from_dict(TINY["dense"]).get(
        "tie_embeddings", False) is False


def _round(w, axis: int, quant):
    """int8 with one scale per slice along ``axis``, written out here."""
    if quant is None:
        return w
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


@functools.partial(jax.jit, static_argnums=(0, 3))
def _direct(seed: int, tokens, targets, quant):
    """The toy model in plain ``jax.numpy``, from the draws up; jitted as
    the reference is, so that both round the weights alike."""
    d, width, eps = TOY["d_model"], TOY["toy_width"], TOY["rms_eps"]
    bf = jnp.bfloat16

    def draw(key, shape, scale, axis=0):
        return _round((jax.random.normal(key, shape) * scale).astype(bf)
                      .astype(jnp.float32), axis, quant)

    def norm(x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    k_embed, _, _, k_layers = jax.random.split(jax.random.PRNGKey(seed), 4)
    emb = draw(k_embed, (TOY["vocab"], d), 0.02, axis=1)   # per row
    x = x0 = emb[tokens]
    for key in jax.random.split(k_layers, TOY["n_layers"]):
        k_in, k_out = jax.random.split(key)
        w_in = draw(k_in, (2 * d, width), 0.1)
        w_out = draw(k_out, (width, d), 0.1)
        h = jnp.concatenate([norm(x), x0], -1)
        x = x + 0.5 * jnp.matmul(jnp.tanh(jnp.matmul(h, w_in, precision=HI)),
                                 w_out, precision=HI)
    logits = jnp.matmul(norm(x), emb.T, precision=HI)       # [n, L, V]
    picked = jnp.take_along_axis(logits[None], targets[..., None], -1)[..., 0]
    return logits.max(-1)[None] - picked, logits.argmax(-1)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_toy_family_runs_as_new_files_only(monkeypatch, quant):
    monkeypatch.setitem(sys.modules, "chipbench.reference.toy", toy_family)
    spec = tiny_spec("dense")
    spec.config.update(reference="toy", model=TOY)
    tokens, targets = _rows(TOY["vocab"])
    gaps, top = cell.reference_gaps(spec, SEED, tokens, targets,
                                    quant=quant)
    want_gaps, want_top = _direct(cell.model_seed(SEED), tokens, targets,
                                  quant)
    np.testing.assert_allclose(gaps, want_gaps, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(top, want_top)
    assert gaps.max() > 0.1        # the target sets are not the best
