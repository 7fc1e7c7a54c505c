"""Per-architecture smoke tests (reduced configs) + model invariants.

Required by the task: every assigned arch instantiates a reduced config,
runs one forward/train step on CPU, asserts output shapes + no NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.models import (decode_step, forward, init_decode_state,
                          init_params, layer_plan, loss_and_metrics)
from repro.models.transformer import prefill

KEY = jax.random.PRNGKey(0)
ALL_ARCHS = sorted(ARCHS)


def _batch(cfg, B=2, S=32):
    batch = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(KEY, 1), (B, S), 0,
                                     cfg.vocab),
    }
    if cfg.frontend != "none":
        batch["frontend"] = jax.random.normal(
            jax.random.fold_in(KEY, 2), (B, cfg.frontend_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, aux = jax.jit(lambda p, b: forward(p, cfg, b["tokens"],
                                               b.get("frontend")))(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert jnp.isfinite(logits).all()

    def loss_fn(p):
        return loss_and_metrics(p, cfg, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert jnp.isfinite(loss)
    gnorm = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_step(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, KEY)
    state = init_decode_state(cfg, batch=2, max_len=16)
    logits, state2 = jax.jit(
        lambda p, s, t: decode_step(p, cfg, s, t))(
            params, state, jnp.array([1, 2], jnp.int32))
    assert logits.shape == (2, cfg.vocab)
    assert jnp.isfinite(logits).all()
    assert jax.tree.structure(state2) == jax.tree.structure(state)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = dataclasses.replace(ARCHS[arch].reduced(), capacity_factor=16.0)
    params = init_params(cfg, KEY)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    fe = batch.get("frontend")
    logits_full, _ = forward(params, cfg, batch["tokens"], fe)
    _, state = prefill(params, cfg, batch["tokens"][:, :-1],
                       max_len=S + cfg.frontend_len + 8, frontend=fe)
    logits_dec, _ = decode_step(params, cfg, state, batch["tokens"][:, -1])
    rel = float(jnp.abs(logits_dec - logits_full[:, -1]).max()) / \
        float(jnp.abs(logits_full[:, -1]).max())
    assert rel < 5e-3, f"{arch}: rel err {rel}"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_successive_decode_steps_match_forward(arch):
    """Two requests of different lengths share a B=2 decode state and take
    N donated steps.  Each step's logits match ``forward`` over the same
    tokens; the longer request's last row lands at T-1 and the shorter one
    crosses a 128-row block; every cache row at or past its length stays
    zero."""
    cfg = dataclasses.replace(ARCHS[arch].reduced(), capacity_factor=16.0)
    params = init_params(cfg, KEY)
    p, n_steps, t_max = cfg.frontend_len, 4, 200
    # cache rows held after each prefill: 126 and T - N
    lens = [126 - p, t_max - n_steps - p]
    toks = jax.random.randint(KEY, (2, lens[1] + n_steps), 0, cfg.vocab)
    fe = _batch(cfg).get("frontend")
    seqs = [toks[b:b + 1, :lens[b] + n_steps] for b in range(2)]
    fes = [None if fe is None else fe[b:b + 1] for b in range(2)]
    full = [forward(params, cfg, seqs[b], fes[b])[0][0] for b in range(2)]
    parts = [prefill(params, cfg, seqs[b][:, :lens[b]], t_max, fes[b])[1]
             for b in range(2)]
    state = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1),
                         *parts)
    step = jax.jit(lambda p_, s, t: decode_step(p_, cfg, s, t),
                   donate_argnums=1)
    for i in range(n_steps):
        tok = jnp.stack([seqs[b][0, lens[b] + i] for b in range(2)])
        logits, state = step(params, state, tok)
        for b in range(2):
            want = full[b][lens[b] + i]
            rel = float(jnp.abs(logits[b] - want).max() /
                        jnp.abs(want).max())
            assert rel < 5e-3, f"{arch}: step {i}, element {b}: {rel}"
        for skey in ("kv", "shared_kv"):
            if skey not in state:
                continue
            kv = state[skey]
            assert (kv["length"] ==
                    jnp.asarray(lens) + p + i + 1).all()
            for b in range(2):
                n = p + lens[b] + i + 1
                assert n <= t_max
                for leaf in ("k", "v"):
                    assert (kv[leaf][:, b, n:] == 0).all()
                    assert (kv[leaf][:, b, n - 1] != 0).any()


def test_layer_plans():
    assert layer_plan(ARCHS["granite-8b"]) == ["attn"] * 36
    assert layer_plan(ARCHS["qwen3-moe-30b-a3b"]) == ["attn_moe"] * 48
    zp = layer_plan(ARCHS["zamba2-1.2b"])
    assert zp.count("mamba2") == 38
    assert zp.count("shared_attn") == 38 // 6
    xp = layer_plan(ARCHS["xlstm-125m"])
    assert xp.count("slstm") == 3 and xp.count("mlstm") == 9


def test_param_counts_match_published_sizes():
    expect = {"qwen2.5-14b": 14.8, "granite-8b": 8.3, "nemotron-4-15b": 15.6,
              "stablelm-3b": 2.8, "zamba2-1.2b": 1.2,
              "qwen3-moe-30b-a3b": 30.1, "internvl2-76b": 70.6}
    for name, bn in expect.items():
        got = ARCHS[name].n_params / 1e9
        assert abs(got - bn) / bn < 0.1, f"{name}: {got:.2f}B vs {bn}B"
    # MoE active params
    assert ARCHS["qwen3-moe-30b-a3b"].n_active_params / 1e9 == pytest.approx(
        2.9, rel=0.15)


def test_moe_aux_loss_uniform_router_is_one():
    """Property: with perfectly uniform routing, the Switch aux loss -> 1."""
    from repro.models.moe import init_moe, moe_block
    p = init_moe(KEY, 32, 8, 64)
    p["router"] = jnp.zeros_like(p["router"])       # uniform probs
    x = jax.random.normal(KEY, (2, 64, 32))
    _, aux = moe_block(p, x, top_k=2)
    assert float(aux) == pytest.approx(1.0, rel=0.05)


def test_shape_applicability_matrix():
    """40 cells: long_500k only for sub-quadratic archs."""
    n_ok = n_skip = 0
    for cfg in ARCHS.values():
        for shape in SHAPES.values():
            ok, reason = shape_applicable(cfg, shape)
            n_ok += ok
            n_skip += not ok
            if shape.name == "long_500k":
                assert ok == cfg.sub_quadratic
    assert n_ok + n_skip == 40
    assert n_skip == 8                              # 8 full-attention archs


def test_vlm_frontend_changes_logits():
    cfg = ARCHS["internvl2-76b"].reduced()
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    l1, _ = forward(params, cfg, batch["tokens"], batch["frontend"])
    l2, _ = forward(params, cfg, batch["tokens"],
                    jnp.zeros_like(batch["frontend"]))
    assert not jnp.allclose(l1, l2)
    assert l1.shape == l2.shape == (2, 32, cfg.vocab)
