"""Unified decoder LM covering all assigned architecture families.

A model is (init_params, forward, loss_and_metrics, init_decode_state,
decode_step) driven entirely by ModelConfig.  Layer stacks are *scanned*
(stacked leaf arrays with a leading layer axis) — essential to keep
dry-run compile times tractable at 48-80 layers and to keep the HLO small
enough to parse for collective bytes.

Families:
  dense / vlm / audio — pre-norm GQA attention + FFN (SwiGLU / squared-ReLU
      / GELU), optional QKV bias, RoPE.  vlm/audio prepend stub frontend
      embeddings (precomputed patch/frame vectors from input_specs).
  moe   — attention + top-k capacity-routed MoE FFN (+ optional shared
      expert), aux load-balance loss.
  hybrid (zamba2) — Mamba-2 backbone; ONE weight-shared attention+FFN block
      applied every ``shared_attn_every`` layers (each application keeps its
      own KV cache at decode).
  ssm (xlstm) — mLSTM blocks with sLSTM every ``slstm_every``.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import constrain
from .attention import (attention_block, attention_decode, init_attention,
                        init_kv_cache)
from .layers import ffn, init_ffn, init_linear, rms_norm
from .mamba2 import (init_mamba2, init_mamba2_state, mamba2_block,
                     mamba2_decode)
from .moe import init_moe, moe_block
from .xlstm import (init_mlstm, init_mlstm_state, init_slstm,
                    init_slstm_state, mlstm_block, mlstm_decode, slstm_block,
                    slstm_decode)

Params = dict
PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# layer plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> list[str]:
    """Block type per layer index."""
    if cfg.family in ("dense", "vlm", "audio"):
        return ["attn"] * cfg.n_layers
    if cfg.family == "moe":
        return ["attn_moe"] * cfg.n_layers
    if cfg.family == "hybrid":
        plan = []
        for i in range(cfg.n_layers):
            plan.append("mamba2")
            if cfg.shared_attn_every and (i + 1) % cfg.shared_attn_every == 0:
                plan.append("shared_attn")
        return plan
    if cfg.family == "ssm":
        k = cfg.slstm_every
        return ["slstm" if (k and i % k == k - 1) else "mlstm"
                for i in range(cfg.n_layers)]
    raise ValueError(f"unknown family {cfg.family}")


def _segments(plan: list[str]) -> list[tuple[str, int]]:
    """Run-length encode the plan into (type, count) scan segments."""
    segs: list[tuple[str, int]] = []
    for t in plan:
        if segs and segs[-1][0] == t:
            segs[-1] = (t, segs[-1][1] + 1)
        else:
            segs.append((t, 1))
    return segs


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_one_layer(key, cfg: ModelConfig, kind: str) -> Params:
    dt = _dtype(cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    if kind in ("attn", "attn_moe", "shared_attn"):
        p = {
            "ln1": jnp.ones((d,), dt),
            "attn": init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads, hd,
                                   cfg.qkv_bias, dt),
            "ln2": jnp.ones((d,), dt),
        }
        if kind == "attn_moe":
            p["moe"] = init_moe(ks[1], d, cfg.n_experts, cfg.d_ff,
                                cfg.moe_shared_ff, cfg.act, dt)
        else:
            p["ffn"] = init_ffn(ks[1], d, cfg.d_ff, cfg.act, dt)
        return p
    if kind == "mamba2":
        return {
            "ln1": jnp.ones((d,), dt),
            "mamba": init_mamba2(ks[0], d, cfg.n_heads, cfg.mamba_head_dim,
                                 cfg.ssm_state, dt),
        }
    if kind == "mlstm":
        return {"ln1": jnp.ones((d,), dt),
                "mlstm": init_mlstm(ks[0], d, cfg.n_heads,
                                    cfg.mlstm_proj_factor, dt)}
    if kind == "slstm":
        return {"ln1": jnp.ones((d,), dt),
                "slstm": init_slstm(ks[0], d, cfg.n_heads, dtype=dt)}
    raise ValueError(kind)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    plan = layer_plan(cfg)
    segs = _segments(plan)
    k_embed, k_head, k_shared, k_layers = jax.random.split(key, 4)

    params: Params = {
        "embed": init_linear(k_embed, (cfg.vocab, cfg.d_model), dt, scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(k_head, (cfg.d_model, cfg.vocab), dt)

    # one stacked tree per block *type* (segments slice into it).  Layer
    # keys are dealt in plan order; each type's layers are initialised in
    # one vmapped call, so the stack is built in place (no per-layer
    # copies to concatenate, and one small program under jit).
    layer_ids: dict[str, list[int]] = {}
    n = 0
    for t, c in segs:
        if t != "shared_attn":
            layer_ids.setdefault(t, []).extend(range(n, n + c))
            n += c
    keys = jax.random.split(k_layers, max(n, 1))
    params["stacks"] = {
        t: jax.vmap(lambda k, _t=t: _init_one_layer(k, cfg, _t))(
            keys[jnp.asarray(ids)])
        for t, ids in layer_ids.items()}
    if any(t == "shared_attn" for t, _ in segs):
        params["shared_attn"] = _init_one_layer(k_shared, cfg, "shared_attn")
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _sp_gather(h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Megatron-SP boundary (only when cfg.seq_parallel): the residual
    stream is sequence-sharded over the model axis; projections are
    weight-sharded over the SAME axis, so the activation must be
    explicitly all-gathered (33 MB bf16) before the column-parallel
    matmuls.  Without this pin GSPMD resolves the conflict by gathering
    the *weights* — full f32 matrices, every layer, every pass: measured
    2.0 TB/step of all-reduce on granite-8b train_4k (EXPERIMENTS.md
    §Perf iteration 2)."""
    if not cfg.seq_parallel:
        return h
    return constrain(h, ("dp", None, None))


def _block_fwd(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
               positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (x_out, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_moe", "shared_attn"):
        h = _sp_gather(rms_norm(x, p["ln1"], cfg.rms_eps), cfg)
        x = x + attention_block(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            positions=positions)
        h = _sp_gather(rms_norm(x, p["ln2"], cfg.rms_eps), cfg)
        if kind == "attn_moe":
            y, aux = moe_block(p["moe"], h, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor)
            x = x + y
        else:
            x = x + ffn(p["ffn"], h, cfg.act)
        return x, aux
    if kind == "mamba2":
        h = _sp_gather(rms_norm(x, p["ln1"], cfg.rms_eps), cfg)
        return x + mamba2_block(p["mamba"], h, n_heads=cfg.n_heads,
                                head_dim=cfg.mamba_head_dim,
                                ssm_state=cfg.ssm_state), aux
    if kind == "mlstm":
        h = _sp_gather(rms_norm(x, p["ln1"], cfg.rms_eps), cfg)
        return x + mlstm_block(p["mlstm"], h, n_heads=cfg.n_heads), aux
    if kind == "slstm":
        h = _sp_gather(rms_norm(x, p["ln1"], cfg.rms_eps), cfg)
        return x + slstm_block(p["slstm"], h, n_heads=cfg.n_heads), aux
    raise ValueError(kind)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            frontend: Optional[jax.Array] = None,
            remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """tokens: [B, S_text] -> (logits [B, S_text, V], aux_loss).

    vlm/audio: ``frontend`` [B, P, d] embeddings are prepended; logits are
    returned only for the text positions.
    """
    x = params["embed"][tokens]                      # [B, S, d]
    prefix = 0
    if frontend is not None:
        prefix = frontend.shape[1]
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    s_total = x.shape[1]
    positions = jnp.arange(s_total)[None, :]

    segs = _segments(layer_plan(cfg))
    offsets: dict[str, int] = {}
    aux_total = jnp.zeros((), jnp.float32)

    for kind, count in segs:
        if kind == "shared_attn":
            for _ in range(count):
                x, aux = _block_fwd(cfg, kind, params["shared_attn"], x,
                                    positions)
                aux_total += aux
            continue
        start = offsets.get(kind, 0)
        offsets[kind] = start + count
        stack = jax.tree.map(lambda a: a[start:start + count],
                             params["stacks"][kind])

        def body(carry, layer_p, _kind=kind):
            x_c, aux_c = carry
            x_n, aux = _block_fwd(cfg, _kind, layer_p, x_c, positions)
            if cfg.seq_parallel:
                # sequence parallelism: the residual stream (and the
                # per-layer saved activation for the scan backward) lives
                # sequence-sharded over the model axis (Megatron-SP).
                x_n = constrain(x_n, ("dp", "model", None))
            return (x_n, aux_c + aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stack)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if prefix:
        x = x[:, prefix:, :]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    return logits, aux_total


def loss_and_metrics(params: Params, cfg: ModelConfig, batch: dict,
                     remat: bool = False) -> tuple[jax.Array, dict]:
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("frontend"), remat=remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + cfg.aux_loss_coef * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "tokens": mask.sum()}


# ---------------------------------------------------------------------------
# prefill (forward + decode-state capture, for serving)
# ---------------------------------------------------------------------------

def _block_prefill(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                   positions: jax.Array) -> tuple[jax.Array, PyTree]:
    if kind in ("attn", "attn_moe", "shared_attn"):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, (k, v) = attention_block(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            positions=positions, return_kv=True)
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps)
        if kind == "attn_moe":
            y, _ = moe_block(p["moe"], h, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
            x = x + y
        else:
            x = x + ffn(p["ffn"], h, cfg.act)
        return x, {"k": k, "v": v}
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if kind == "mamba2":
        y, st = mamba2_block(p["mamba"], h, n_heads=cfg.n_heads,
                             head_dim=cfg.mamba_head_dim,
                             ssm_state=cfg.ssm_state, return_state=True)
    elif kind == "mlstm":
        y, st = mlstm_block(p["mlstm"], h, n_heads=cfg.n_heads,
                            return_state=True)
    elif kind == "slstm":
        y, st = slstm_block(p["slstm"], h, n_heads=cfg.n_heads,
                            return_state=True)
    else:
        raise ValueError(kind)
    return x + y, st


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            max_len: int, frontend: Optional[jax.Array] = None
            ) -> tuple[jax.Array, PyTree]:
    """Process the full prompt; return (last-token logits [B,V], decode
    state sized for ``max_len``) — the serving engine's prefill task."""
    x = params["embed"][tokens]
    if frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    bsz, s_total = x.shape[0], x.shape[1]
    if max_len < s_total:
        raise ValueError(f"max_len {max_len} < prompt {s_total}")
    positions = jnp.arange(s_total)[None, :]

    segs = _segments(layer_plan(cfg))
    offsets: dict[str, int] = {}
    collected: dict[str, list] = {}

    for kind, count in segs:
        skey = _STATE_KEY[kind]
        if kind == "shared_attn":
            for _ in range(count):
                x, st = _block_prefill(cfg, kind, params["shared_attn"], x,
                                       positions)
                collected.setdefault(skey, []).append(st)
            continue
        start = offsets.get(kind, 0)
        offsets[kind] = start + count
        stack = jax.tree.map(lambda a: a[start:start + count],
                             params["stacks"][kind])

        def body(x_c, layer_p, _kind=kind):
            x_n, st = _block_prefill(cfg, _kind, layer_p, x_c, positions)
            return x_n, st

        x, sts = jax.lax.scan(body, x, stack)     # sts: stacked [count, ...]
        collected.setdefault(skey, []).append(sts)

    # assemble the decode-state pytree (segment stacks in plan order).
    # shared_attn parts are per-application (unstacked) -> stack; scanned
    # segment parts are already stacked [count, ...] -> concat.
    state: dict[str, PyTree] = {}
    for skey, parts in collected.items():
        if skey == "shared_kv":
            state[skey] = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
        elif len(parts) == 1:
            state[skey] = parts[0]
        else:
            state[skey] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *parts)

    # pad KV caches out to max_len and attach lengths
    length = jnp.full((bsz,), s_total, jnp.int32)
    for skey in ("kv", "shared_kv"):
        if skey not in state:
            continue
        kv = state[skey]
        pad = max_len - s_total
        state[skey] = {
            "k": jnp.pad(kv["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(kv["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            "length": jnp.broadcast_to(length, kv["k"].shape[:1] + (bsz,)),
        }

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, -1] @ head).astype(jnp.float32)
    return logits, state


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    """Stacked per-type decode state mirroring the layer plan."""
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    plan = layer_plan(cfg)
    state: dict[str, PyTree] = {}
    n_attn = sum(1 for t in plan if t in ("attn", "attn_moe"))
    if n_attn:
        one = init_kv_cache(batch, max_len, cfg.n_kv_heads, hd, dt)
        state["kv"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_attn,) + a.shape), one)
    n_shared = sum(1 for t in plan if t == "shared_attn")
    if n_shared:
        one = init_kv_cache(batch, max_len, cfg.n_kv_heads, hd, dt)
        state["shared_kv"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_shared,) + a.shape), one)
    n_mamba = sum(1 for t in plan if t == "mamba2")
    if n_mamba:
        one = init_mamba2_state(batch, cfg.n_heads, cfg.mamba_head_dim,
                                cfg.ssm_state, dt)
        state["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_mamba,) + a.shape), one)
    n_ml = sum(1 for t in plan if t == "mlstm")
    if n_ml:
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        one = init_mlstm_state(batch, cfg.n_heads, di // cfg.n_heads, dt)
        state["mlstm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_ml,) + a.shape), one)
    n_sl = sum(1 for t in plan if t == "slstm")
    if n_sl:
        one = init_slstm_state(batch, cfg.d_model, dt)
        state["slstm"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_sl,) + a.shape), one)
    return state


_STATE_KEY = {"attn": "kv", "attn_moe": "kv", "shared_attn": "shared_kv",
              "mamba2": "mamba", "mlstm": "mlstm", "slstm": "slstm"}


def _block_decode(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                  st: PyTree, layer: jax.Array | None = None
                  ) -> tuple[jax.Array, PyTree]:
    """One layer's decode.  Attention kinds take the whole cache stack and
    ``layer``, their index into it; the others take their own state."""
    if kind in ("attn", "attn_moe", "shared_attn"):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = attention_decode(
            p["attn"], h, st, layer, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta)
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps)
        if kind == "attn_moe":
            y, _ = moe_block(p["moe"], h, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
            x = x + y
        else:
            x = x + ffn(p["ffn"], h, cfg.act)
        return x, st
    if kind == "mamba2":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = mamba2_decode(p["mamba"], h, st, n_heads=cfg.n_heads,
                              head_dim=cfg.mamba_head_dim,
                              ssm_state=cfg.ssm_state)
        return x + y, st
    if kind == "mlstm":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = mlstm_decode(p["mlstm"], h, st, n_heads=cfg.n_heads)
        return x + y, st
    if kind == "slstm":
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, st = slstm_decode(p["slstm"], h, st, n_heads=cfg.n_heads)
        return x + y, st
    raise ValueError(kind)


def decode_step(params: Params, cfg: ModelConfig, state: PyTree,
                tokens: jax.Array) -> tuple[jax.Array, PyTree]:
    """One decode step.  tokens: [B] int32 -> (logits [B, V], new state).

    Scans over each stacked layer group, so compile time stays
    O(#segments), not O(#layers).  The KV cache stacks ride the scan carry
    whole and each layer writes its one new row into them (in place when
    the caller donates ``state``); the small recurrent states are the scan
    input and output of their segment.
    """
    x = params["embed"][tokens][:, None, :]          # [B, 1, d]
    segs = _segments(layer_plan(cfg))
    state_off: dict[str, int] = {}    # running offset into each state stack
    param_off: dict[str, int] = {}    # running offset into each param stack
    new_state = dict(state)

    def layer_params(layer_p):
        return params["shared_attn"] if layer_p is None else layer_p

    for kind, count in segs:
        skey = _STATE_KEY[kind]
        s0 = state_off.get(skey, 0)
        state_off[skey] = s0 + count
        if kind == "shared_attn":     # one weight-shared block
            p_stack = None
        else:
            p0 = param_off.get(kind, 0)
            param_off[kind] = p0 + count
            p_stack = jax.tree.map(lambda a: a[p0:p0 + count],
                                   params["stacks"][kind])

        if skey in ("kv", "shared_kv"):
            def body(carry, inp, _kind=kind):
                x_c, kv = carry
                layer_p, layer = inp
                return _block_decode(cfg, _kind, layer_params(layer_p), x_c,
                                     kv, layer), None

            layers = s0 + jnp.arange(count, dtype=jnp.int32)
            (x, new_state[skey]), _ = jax.lax.scan(
                body, (x, new_state[skey]), (p_stack, layers))
            continue

        st_stack = jax.tree.map(lambda a: a[s0:s0 + count], state[skey])

        def body(x_c, inp, _kind=kind):
            layer_p, sl = inp
            return _block_decode(cfg, _kind, layer_params(layer_p), x_c, sl)

        x, st_new = jax.lax.scan(body, x, (p_stack, st_stack))
        new_state[skey] = jax.tree.map(
            lambda full, new, _s0=s0: jax.lax.dynamic_update_slice_in_dim(
                full, new.astype(full.dtype), _s0, axis=0),
            new_state[skey], st_new)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return logits, new_state
