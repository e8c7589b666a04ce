"""Ahead-of-time compiles of every Pallas kernel for a TPU v5e chip.

The TPU compiler is installed alongside JAX, and it compiles for a chip
that is described rather than attached.  Interpret mode (what every other
kernel test runs) cannot see Mosaic's tiling and VMEM rules; these
compiles can, at Qwen2.5-0.5B widths in bf16: d_model 896, d_ff 4864,
14 query heads over 2 KV heads of head_dim 64.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite's workers all
import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.fused_kv_proj.kernel import kv_proj_pallas
from repro.kernels.fused_mlp.kernel import fused_mlp_pallas
from repro.kernels.fused_rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.fused_softmax.kernel import softmax_pallas
from repro.kernels.tiled_matmul.kernel import matmul_pallas

D, F, H, KV, HD = 896, 4864, 14, 2, 64
ROWS = 128          # one MXU row block of tokens
SLOTS, S = 4, 2048  # decode: scheduler slots × cache length

# kernel → (fn, [(shape, dtype), ...]) in the kernel's own argument layout
CASES = {
    "tiled_matmul": (matmul_pallas, [((ROWS, D), "bf16"), ((D, F), "bf16")]),
    "fused_rmsnorm": (rmsnorm_pallas, [((ROWS, D), "bf16"), ((D,), "bf16")]),
    "fused_mlp": (fused_mlp_pallas, [((ROWS, D), "bf16"), ((D, F), "bf16"),
                                     ((D, F), "bf16")]),
    "fused_kv_proj": (kv_proj_pallas, [((ROWS, D), "bf16"),
                                       ((D, 2 * KV * HD), "bf16"),
                                       ((2 * KV * HD,), "bf16")]),
    "fused_softmax": (softmax_pallas, [((8, 151936), "bf16")]),
    "decode_attention": (decode_attention_pallas, [
        ((SLOTS, KV, H // KV, HD), "bf16"), ((SLOTS, KV, S, HD), "bf16"),
        ((SLOTS, KV, S, HD), "bf16"), ((1,), "i32")]),
}
_DTYPES = {"bf16": jnp.bfloat16, "i32": jnp.int32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, _DTYPES[dt], sharding=one_chip)
            for shape, dt in specs]
    compiled = (jax.jit(functools.partial(fn, interpret=False))
                .lower(*args).compile())
    # Mosaic kernel present, i.e. not lowered as plain XLA ops
    assert "tpu_custom_call" in compiled.as_text()
