"""The Pallas kernels of the serving path compile for a TPU v5e.

Each case lowers and compiles one kernel at a real model shape for a
described (not attached) ``v5e:2x2`` topology: the TPU compiler runs here
and refuses what the chip would refuse (tiling, VMEM, unsupported
primitives), which the interpret-mode tests in ``test_kernels.py`` cannot
show.  Nothing runs, so these say nothing about results or times.

The topology is described inside a fixture, never while the module is
imported: only one process may load the TPU library, and every test
worker imports this file.  Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip is written to the
        # persistent cache but cannot be read back without the chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, shapes, dtypes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in zip(shapes, dtypes)]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_stablelm_3b_prefill(one_chip):
    shape = (1, 32, 256, 80)                      # [B, H, S, D], D=80
    hlo = _compile(lambda q, k, v: flash_attention_pallas(q, k, v),
                   [shape] * 3, [jnp.bfloat16] * 3, one_chip)
    assert "tpu_custom_call" in hlo


def test_matmul_compiles_at_1024_bf16(one_chip):
    hlo = _compile(matmul_pallas, [(1024, 1024)] * 2, [jnp.bfloat16] * 2,
                   one_chip)
    assert "tpu_custom_call" in hlo


# ssd_scan_pallas(x [B,S,H,D], a [B,S,H], b [B,S,N], c [B,S,N]) at S=256.
# xlstm-125m's mLSTM folds its 4 heads into the batch (head dim 384, keys
# and queries as B and C) and runs the scan twice: once over the values
# (D=384) and once over the input gate for the normaliser (D=1).
# zamba2-1.2b's Mamba-2 layer runs 32 heads of 128 with state N=64.
SSD_SHAPES = {
    "xlstm-125m-values": ((4, 256, 1, 384), (4, 256, 1), (4, 256, 384)),
    "xlstm-125m-normaliser": ((4, 256, 1, 1), (4, 256, 1), (4, 256, 384)),
    "zamba2-1.2b": ((1, 256, 32, 128), (1, 256, 32), (1, 256, 64)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(SSD_SHAPES))
def test_ssd_scan_compiles_at_model_shapes(one_chip, case, dtype):
    x, a, bc = SSD_SHAPES[case]
    hlo = _compile(lambda x, a, b, c: ssd_scan_pallas(x, a, b, c),
                   [x, a, bc, bc], [dtype] * 4, one_chip)
    assert "tpu_custom_call" in hlo
