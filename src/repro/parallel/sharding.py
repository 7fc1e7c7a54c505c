"""Sharding rules: leaf-name-driven PartitionSpecs for params, optimizer
state, batches and decode state.

Mesh axes:
  single-pod:  ("data", "model") = (16, 16)          — 256 chips
  multi-pod:   ("pod", "data", "model") = (2, 16, 16) — 512 chips

Parallelism mapping:
  DP  — batch over ("pod", "data") (hierarchical all-reduce: ICI inside a
        pod, DCN across pods).
  TP  — Megatron column/row sharding over "model": wq/wk/wv/w_gate/w_up
        column-sharded, wo/w_down row-sharded; vocab-sharded embedding and
        lm_head.
  EP  — expert stacks [E, ...] sharded over "model" (dispatch all-to-all
        stays inside the pod's ICI domain).
  SP  — long-context decode KV caches sharded over "model" on the
        *sequence* dim; softmax over the sharded dim lowers to cheap
        per-(b,h) all-reduces.
  ZeRO-1 — optimizer moments additionally sharded over "data" on the
        first replicated dim that divides.

Every spec is *sanitized* against real dim sizes: an axis that does not
divide the dim is dropped (replicated) rather than failing, so the same
rules serve the full configs, the reduced smoke configs, and any mesh.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# base spec per leaf name, for the *unstacked* (per-layer) shape
_RULES: dict[str, tuple[Optional[str], ...]] = {
    # embeddings / head
    "embed": ("model", None),
    "lm_head": (None, "model"),
    "final_norm": (None,),
    # attention
    "wq": (None, "model"), "wk": (None, "model"), "wv": (None, "model"),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    "wo": ("model", None),
    # FFN
    "w_gate": (None, "model"), "w_up": (None, "model"),
    "w_down": ("model", None),
    # MoE (leading E axis = expert parallelism)
    "router": (None, None),
    "experts_gate": ("model", None, None),
    "experts_up": ("model", None, None),
    "experts_down": ("model", None, None),
    # Mamba-2
    "wx": (None, "model"), "wz": (None, "model"),
    "wb": (None, None), "wc": (None, None), "wdt": (None, "model"),
    "conv_w": (None, "model"), "dt_bias": ("model",), "a_log": ("model",),
    "norm_z": ("model",), "w_out": ("model", None),
    # mLSTM
    "w_x": (None, "model"), "w_gate_proj": (None, "model"),
    "w_if": (None, None), "norm_h": ("model",),
    # sLSTM
    "w_i": (None, "model"), "w_f": (None, "model"),
    "w_z": (None, "model"), "w_o": (None, "model"),
    "r_gates": (None, "model"),
    "w_up_a": (None, "model"), "w_up_b": (None, "model"),
    # norms
    "ln1": (None,), "ln2": (None,),
}


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def sanitize(spec: tuple, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes that don't divide their dim; trim/pad rank."""
    spec = tuple(spec)[:len(shape)] + (None,) * (len(shape) - len(spec))
    out = []
    for dim, axis in zip(shape, spec):
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def _is_stacked(path) -> bool:
    return any(getattr(p, "key", None) == "stacks" for p in path)


def param_specs(params_shape: PyTree, mesh: Mesh, *,
                fsdp: bool = False) -> PyTree:
    """PartitionSpec tree for a params(-shaped) tree.  Stacked leaves (under
    "stacks") get a leading None for the layer axis.

    ``fsdp``: additionally shard each leaf over "data" on its first free
    dim (ZeRO-3 / FSDP) — required when bf16 params / TP don't fit HBM
    (e.g. the 70B VLM backbone).  XLA then all-gathers each layer's weights
    just-in-time; we never shard params over "pod" (DCN gathers per layer
    would be ruinous)."""
    def spec_for(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        # (§Perf cell-2 note: replicating sLSTM weights to avoid its
        # per-timestep all-reduces was tried and REFUTED — it trades tiny
        # ARs for 16x redundant per-device work; TP-sharded sLSTM stays.)
        base = _RULES.get(name)
        if base is None:
            base = (None,) * len(shape)
        elif _is_stacked(path):
            base = (None,) + tuple(base)
        spec = tuple(sanitize(base, shape, mesh))
        if fsdp:
            axes = list(spec) + [None] * (len(shape) - len(spec))
            for i, (dim, ax) in enumerate(zip(shape, axes)):
                if ax is None and dim > 1 and dim % _axis_size(mesh, "data") == 0:
                    axes[i] = "data"
                    break
            spec = tuple(axes)
        return sanitize(spec, shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def opt_moment_specs(params_shape: PyTree, mesh: Mesh) -> PyTree:
    """ZeRO-1: like param specs but with "data" folded into the first
    still-replicated dim that divides — optimizer memory scales 1/DP."""
    base = param_specs(params_shape, mesh)

    def zero1(path, leaf, spec):
        shape = tuple(leaf.shape)
        axes = list(spec)
        axes += [None] * (len(shape) - len(axes))
        for i, (dim, ax) in enumerate(zip(shape, axes)):
            if ax is None and dim % _axis_size(mesh, "data") == 0 and dim > 1:
                axes[i] = "data"
                break
        return sanitize(tuple(axes), shape, mesh)

    return jax.tree_util.tree_map_with_path(zero1, params_shape, base)


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_specs(batch_shape: PyTree, mesh: Mesh) -> PyTree:
    """Token batches: batch dim over DP axes, rest replicated."""
    dp = dp_axes(mesh)

    def spec_for(leaf):
        shape = tuple(leaf.shape)
        return sanitize((dp,) + (None,) * (len(shape) - 1), shape, mesh)

    return jax.tree.map(spec_for, batch_shape)


def decode_state_specs(state_shape: PyTree, mesh: Mesh) -> PyTree:
    """Decode caches/states.  Leaves live under stacked layer groups with a
    leading L axis: [L, B, ...].  KV caches [L, B, T, Hkv, D] shard B over
    DP and T (sequence) over "model" (SP for long context); recurrent
    states [L, B, H, ...] shard B over DP and H over "model"."""
    dp = dp_axes(mesh)

    def spec_for(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        if name in ("k", "v") and len(shape) == 5:      # [L,B,T,Hkv,D]
            return sanitize((None, dp, "model", None, None), shape, mesh)
        if name == "length":
            return sanitize((None, dp), shape, mesh)
        if name in ("ssm", "C") and len(shape) == 5:    # [L,B,H,D,N]
            return sanitize((None, dp, "model", None, None), shape, mesh)
        if name == "conv" and len(shape) == 4:          # [L,B,W-1,Di]
            return sanitize((None, dp, None, "model"), shape, mesh)
        if name == "n" and len(shape) == 4:             # [L,B,H,N]
            return sanitize((None, dp, "model", None), shape, mesh)
        if len(shape) == 3:                             # slstm [L,B,d]
            return sanitize((None, dp, "model"), shape, mesh)
        return sanitize((None, dp) + (None,) * (len(shape) - 2), shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, state_shape)


def to_named(spec_tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# In-model sharding constraints.
#
# Model code must not depend on a concrete mesh, but long-context decode
# needs activation pins (e.g. "keep the KV cache sequence-sharded") or GSPMD
# picks catastrophic reshards.  ``sharding_ctx(mesh)`` is entered by the
# launcher/dry-run around tracing; ``constrain(x, axes)`` then applies a
# sanitized with_sharding_constraint, and is a no-op outside the context
# (CPU unit tests, single-device runs).  The sentinel "dp" expands to the
# mesh's data-parallel axes.
# ---------------------------------------------------------------------------

import contextlib

_ACTIVE_MESH: list[Mesh] = []


@contextlib.contextmanager
def sharding_ctx(mesh: Mesh):
    _ACTIVE_MESH.append(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.pop()


def shards_of(dim: int, axis: str) -> int:
    """How many shards the active mesh cuts a dim of size ``dim`` into
    over ``axis``: 1 outside ``sharding_ctx``, or where the axis does not
    divide the dim (``sanitize`` then replicates it)."""
    if not _ACTIVE_MESH:
        return 1
    n = _ACTIVE_MESH[-1].shape.get(axis, 1)
    return n if dim % n == 0 else 1


def constrain(x: jax.Array, axes: tuple) -> jax.Array:
    if not _ACTIVE_MESH:
        return x
    mesh = _ACTIVE_MESH[-1]
    resolved = tuple(dp_axes(mesh) if a == "dp" else a for a in axes)
    spec = sanitize(resolved, tuple(x.shape), mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def pin_stack_cotangent(tree: PyTree, *, stacked: bool = True) -> PyTree:
    """Identity on the forward pass; on the backward pass constrains the
    weight-gradient cotangent to the ZeRO sharding (param spec + "data" on
    the first free dim).

    Why: the scan-over-layers backward accumulates the xs-cotangent (the
    stacked weight grads) at the sharding of the *gathered* per-layer
    weights — for FSDP'd params that is a model-only-sharded full-size
    buffer (tens of GB for a 70B model).  Applied to the per-layer slice
    *inside* the scan body, the constraint scatters each layer's gradient
    before it is accumulated — the ZeRO-3 backward (per-layer
    reduce-scatter); the loop buffer then carries only the scattered
    shard."""
    if not _ACTIVE_MESH:
        return tree
    mesh = _ACTIVE_MESH[-1]

    def leaf_spec(path, leaf):
        name = _leaf_name(path)
        base = _RULES.get(name, (None,) * (len(leaf.shape) - (1 if stacked else 0)))
        axes = ([None] if stacked else []) + list(base)
        axes += [None] * (len(leaf.shape) - len(axes))
        for i, (dim, ax) in enumerate(zip(leaf.shape, axes)):
            if ax is None and dim > 1 and dim % _axis_size(mesh, "data") == 0:
                axes[i] = "data"
                break
        return sanitize(tuple(axes), tuple(leaf.shape), mesh)

    specs = jax.tree_util.tree_map_with_path(leaf_spec, tree)

    @jax.custom_vjp
    def _pin(t):
        return t

    def _fwd(t):
        return t, None

    def _bwd(_, ct):
        return (jax.tree.map(
            lambda c, s: jax.lax.with_sharding_constraint(c, s), ct, specs),)

    _pin.defvjp(_fwd, _bwd)
    return _pin(tree)
