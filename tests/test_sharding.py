"""Sharding rules + a small-scale dry-run executed in a subprocess (the
device-count flag must not leak into this test process)."""
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.models import init_params
from repro.parallel.sharding import sanitize


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_sanitize_drops_nondivisible():
    mesh = _FakeMesh({"data": 4, "model": 8})
    assert sanitize(("model", None), (16, 3), mesh) == P("model", None)
    assert sanitize(("model", None), (12, 3), mesh) == P(None, None)
    assert sanitize((("data", "model"), None), (32, 3), mesh) == \
        P(("data", "model"), None)
    assert sanitize((("data", "model"), None), (16, 3), mesh) == P(None, None)


def test_sanitize_pads_rank():
    mesh = _FakeMesh({"data": 2, "model": 2})
    assert sanitize(("model",), (4, 6, 8), mesh) == P("model", None, None)


SUBPROCESS_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import dataclasses, json, math, re
    import jax, jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.launch.mesh import auto_mesh
    from repro.models import init_params, init_decode_state
    from repro.optim import init_opt_state, AdamWConfig
    from repro.parallel import (param_specs, opt_moment_specs, batch_specs,
                                decode_state_specs, to_named, sharding_ctx)
    from repro.train import make_train_step, make_decode_step

    mesh = auto_mesh((4, 4), ("data", "model"))
    cfg = dataclasses.replace(ARCHS["{arch}"].reduced(), dtype="bfloat16")
    key = jax.random.PRNGKey(0)
    p_shape = jax.eval_shape(lambda: init_params(cfg, key))
    p_spec = param_specs(p_shape, mesh)
    results = {{}}

    # train step
    opt_shape = jax.eval_shape(init_opt_state, p_shape)
    moments = opt_moment_specs(p_shape, mesh)
    o_spec = {{"m": moments, "v": moments, "step": jax.sharding.PartitionSpec()}}
    if "master" in opt_shape:
        o_spec["master"] = moments
    batch = {{"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}}
    if cfg.frontend != "none":
        batch["frontend"] = jax.ShapeDtypeStruct(
            (8, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
    b_spec = batch_specs(batch, mesh)
    step = make_train_step(cfg, AdamWConfig(), remat=True)
    with mesh, sharding_ctx(mesh):
        c = jax.jit(step, in_shardings=to_named((p_spec, o_spec, b_spec), mesh)
                    ).lower(p_shape, opt_shape, batch).compile()
    results["train_flops"] = c.cost_analysis().get("flops", 0.0)

    # decode step, its state donated as the serving engine donates it
    st_shape = jax.eval_shape(lambda: init_decode_state(cfg, 8, 64))
    st_spec = decode_state_specs(st_shape, mesh)
    toks = jax.ShapeDtypeStruct((8,), jnp.int32)
    dstep = make_decode_step(cfg)
    with mesh, sharding_ctx(mesh):
        c2 = jax.jit(dstep, in_shardings=to_named(
            (p_spec, st_spec, batch_specs(toks, mesh)), mesh),
            donate_argnums=(1,)).lower(p_shape, st_shape, toks).compile()
    results["decode_ok"] = True
    # KV cache stack shapes, and the element count of each all-gather
    results["kv_stacks"] = [st_shape[k]["k"].shape
                            for k in ("kv", "shared_kv") if k in st_shape]
    gather = r"= \\w+\\[([\\d,]*)\\][^=]* all-gather(?:-start)?\\("
    results["decode_gathers"] = [
        math.prod(int(d) for d in dims.split(",") if d)
        for dims in re.findall(gather, c2.as_text())]
    print(json.dumps(results))
""")


@pytest.fixture(scope="module")
def dryrun():
    """Runs the 16-device dry run of an arch in a subprocess, once per
    arch for this module's tests."""
    runs = {}

    def get(arch):
        if arch not in runs:
            env = dict(os.environ, PYTHONPATH=os.path.join(
                os.path.dirname(__file__), "..", "src"))
            out = subprocess.run(
                [sys.executable, "-c", SUBPROCESS_DRYRUN.format(arch=arch)],
                capture_output=True, text=True, env=env, timeout=600)
            assert out.returncode == 0, out.stderr[-3000:]
            runs[arch] = json.loads(out.stdout.strip().splitlines()[-1])
        return runs[arch]

    return get


@pytest.mark.parametrize("arch", ["granite-8b", "qwen3-moe-30b-a3b",
                                  "zamba2-1.2b", "xlstm-125m",
                                  "internvl2-76b"])
def test_reduced_dryrun_on_16_fake_devices(arch, dryrun):
    """lower+compile of train AND decode for a reduced config on a real
    (4,4) mesh — the shape-divisibility/sharding logic must hold end to
    end, not just on the production mesh."""
    assert dryrun(arch)["decode_ok"]


@pytest.mark.parametrize("arch", ["granite-8b", "qwen3-moe-30b-a3b",
                                  "zamba2-1.2b"])
def test_sp_decode_gathers_no_cache_on_16_fake_devices(arch, dryrun):
    """With the cache sequence-sharded over "model", the decode step's new
    K/V row stays a shard-local write: no all-gather in the compiled step
    brings a device as much as one layer's share of a KV cache stack."""
    res = dryrun(arch)
    assert res["kv_stacks"]
    for _layers, *layer_shape in res["kv_stacks"]:
        share = math.prod(layer_shape) // 16
        assert max(res["decode_gathers"], default=0) < share, res


def test_param_specs_cover_all_leaves():
    """Every param leaf of every arch gets a spec whose rank matches."""
    from repro.parallel import param_specs
    mesh = _FakeMesh({"data": 4, "model": 4})
    for name, cfg in ARCHS.items():
        r = cfg.reduced()
        p_shape = jax.eval_shape(lambda r=r: init_params(r, jax.random.PRNGKey(0)))
        specs = param_specs(p_shape, mesh)
        leaves_p = jax.tree.leaves(p_shape)
        leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)
        for lp, ls in zip(leaves_p, leaves_s):
            assert len(ls) <= len(lp.shape), (name, lp.shape, ls)
